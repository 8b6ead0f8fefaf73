"""Plain SSZ for the reference state transition: decoding and
``hash_tree_root`` of the types ``reference/deneb.py`` describes, from
the SSZ specification alone (``ssz/simple-serialize.md``), importing
nothing of the program.

Values are plain python: ints, bools, ``bytes``, lists and dicts; lists
of uint64 decode to ``array('Q')`` and lists of uint8 to ``bytearray``
so that a 2^20-entry registry column packs back to bytes at C speed.  A
list of fixed-size containers marked ``records`` (the validator
registry) stays as its serialized bytes (:class:`Records`).

Roots memoize by content, which leaves them exact: a full subtree of
``2^SLAB_DEPTH`` chunks is hashed once per distinct content, and a
record once per distinct record.  Only what changed between two roots of
a state is hashed again.
"""
import hashlib
import struct
from array import array

SLAB_DEPTH = 10             # chunks per memoized subtree: 2^10 = 32 KiB
ZERO = [b"\x00" * 32]
for _ in range(64):
    ZERO.append(hashlib.sha256(ZERO[-1] + ZERO[-1]).digest())

_slabs = {}                 # full-subtree content -> root
_records = {}               # record bytes -> root


def clear_memo():
    _slabs.clear()
    _records.clear()


def sha256(data) -> bytes:
    return hashlib.sha256(data).digest()


def _depth(limit_chunks) -> int:
    return max(limit_chunks - 1, 0).bit_length()


def _subtree(data, k) -> bytes:
    """Root of ``data`` (at most 2^k chunks), zero-padded to 2^k."""
    full = k == SLAB_DEPTH
    if full:
        hit = _slabs.get(data)
        if hit is not None:
            return hit
    layer = [data[i:i + 32] for i in range(0, len(data), 32)]
    for level in range(k):
        if len(layer) % 2:
            layer.append(ZERO[level])
        layer = [sha256(layer[i] + layer[i + 1])
                 for i in range(0, len(layer), 2)]
    if full:
        _slabs[bytes(data)] = layer[0]
    return layer[0]


def merkleize(data, limit_chunks) -> bytes:
    """Merkle root of the 32-byte chunks of ``data`` (zero-padded to a
    whole chunk), in a tree for ``limit_chunks`` chunks."""
    data = bytes(data)
    if len(data) % 32:
        data += b"\x00" * (32 - len(data) % 32)
    n = len(data) // 32
    assert n <= limit_chunks, (n, limit_chunks)
    depth = _depth(limit_chunks)
    if n == 0:
        return ZERO[depth]
    k = min(depth, SLAB_DEPTH)
    step = 32 << k
    layer = [_subtree(data[i:i + step], k) for i in range(0, len(data), step)]
    for level in range(k, depth):
        if len(layer) % 2:
            layer.append(ZERO[level])
        layer = [sha256(layer[i] + layer[i + 1])
                 for i in range(0, len(layer), 2)]
    return layer[0]


def mix_in_length(root, length) -> bytes:
    return sha256(root + length.to_bytes(32, "little"))


# ---------------------------------------------------------------------------
# Types
# ---------------------------------------------------------------------------

class Uint:
    basic = True

    def __init__(self, size):
        self.size = size

    def fixed(self):
        return self.size

    def decode(self, data):
        assert len(data) == self.size
        return int.from_bytes(data, "little")

    def pack(self, v) -> bytes:
        return v.to_bytes(self.size, "little")

    def root(self, v):
        return self.pack(v).ljust(32, b"\x00")


class Boolean(Uint):
    def __init__(self):
        super().__init__(1)

    def decode(self, data):
        assert data in (b"\x00", b"\x01")
        return data == b"\x01"

    def pack(self, v) -> bytes:
        return b"\x01" if v else b"\x00"


class ByteVector:
    basic = False

    def __init__(self, n):
        self.n = n

    def fixed(self):
        return self.n

    def decode(self, data):
        assert len(data) == self.n
        return bytes(data)

    def root(self, v):
        assert len(v) == self.n
        if self.n <= 32:            # one chunk: it is its own root
            return bytes(v).ljust(32, b"\x00")
        return merkleize(v, (self.n + 31) // 32)


def _roots(elem, values) -> bytes:
    """The element roots of a composite sequence, end to end."""
    if isinstance(elem, ByteVector) and elem.n == 32:
        return b"".join(values)
    return b"".join(elem.root(x) for x in values)


class ByteList:
    basic = False

    def __init__(self, limit):
        self.limit = limit

    def fixed(self):
        return None

    def decode(self, data):
        assert len(data) <= self.limit
        return bytes(data)

    def root(self, v):
        return mix_in_length(merkleize(v, (self.limit + 31) // 32), len(v))


def _pack_basic(elem, values) -> bytes:
    if isinstance(values, (bytearray, bytes)):
        assert elem.size == 1
        return bytes(values)
    if isinstance(values, array):
        assert values.itemsize == elem.size
        return values.tobytes()
    return b"".join(elem.pack(v) for v in values)


def _decode_basic(elem, data):
    if elem.size == 8 and not isinstance(elem, Boolean):
        out = array("Q")
        out.frombytes(data)
        return out
    if elem.size == 1 and not isinstance(elem, Boolean):
        return bytearray(data)
    s = elem.size
    return [elem.decode(data[i:i + s]) for i in range(0, len(data), s)]


def _decode_elements(elem, data):
    size = elem.fixed()
    if size is not None:
        assert len(data) % size == 0
        if elem.basic:
            return _decode_basic(elem, data)
        return [elem.decode(data[i:i + size])
                for i in range(0, len(data), size)]
    if not data:
        return []
    first = struct.unpack_from("<I", data, 0)[0]
    assert first % 4 == 0 and first <= len(data)
    offsets = [struct.unpack_from("<I", data, 4 * i)[0]
               for i in range(first // 4)] + [len(data)]
    assert offsets == sorted(offsets)
    return [elem.decode(data[a:b]) for a, b in zip(offsets, offsets[1:])]


class Vector:
    basic = False

    def __init__(self, elem, n):
        self.elem, self.n = elem, n

    def fixed(self):
        s = self.elem.fixed()
        return None if s is None else s * self.n

    def decode(self, data):
        out = _decode_elements(self.elem, data)
        assert len(out) == self.n
        return out

    def root(self, v):
        assert len(v) == self.n
        if self.elem.basic:
            return merkleize(_pack_basic(self.elem, v),
                             (self.n * self.elem.size + 31) // 32)
        return merkleize(_roots(self.elem, v), self.n)


class List:
    basic = False

    def __init__(self, elem, limit):
        self.elem, self.limit = elem, limit

    def fixed(self):
        return None

    def decode(self, data):
        out = _decode_elements(self.elem, data)
        assert len(out) <= self.limit
        return out

    def root(self, v):
        assert len(v) <= self.limit
        if self.elem.basic:
            chunks = (self.limit * self.elem.size + 31) // 32
            return mix_in_length(
                merkleize(_pack_basic(self.elem, v), chunks), len(v))
        return mix_in_length(merkleize(_roots(self.elem, v), self.limit),
                             len(v))


class EmptyList(List):
    """A list the reference's cell never fills: decoding asserts it is
    empty, so its element type is never needed."""

    def __init__(self, limit):
        super().__init__(None, limit)

    def decode(self, data):
        assert len(data) == 0, "an operation list the reference " \
            "does not process is not empty"
        return []

    def root(self, v):
        assert len(v) == 0
        return mix_in_length(ZERO[_depth(self.limit)], 0)


class Records(List):
    """``List[container, limit]`` of a fixed-size container kept as its
    serialized bytes (``value.raw``, ``len(value)``).  Immutable; its
    root is computed once."""

    def decode(self, data):
        size = self.elem.fixed()
        assert size is not None and len(data) % size == 0
        assert len(data) // size <= self.limit
        return RecordList(self.elem, bytes(data))

    def root(self, v):
        if v.root is None:
            size = self.elem.fixed()
            raw, out = v.raw, []
            for i in range(0, len(raw), size):
                rec = raw[i:i + size]
                r = _records.get(rec)
                if r is None:
                    r = _records[rec] = self.elem.root(self.elem.decode(rec))
                out.append(r)
            v.root = mix_in_length(merkleize(b"".join(out), self.limit),
                                   len(v))
        return v.root


class RecordList:
    def __init__(self, elem, raw):
        self.elem, self.raw, self.root = elem, raw, None
        self.size = elem.fixed()

    def __len__(self):
        return len(self.raw) // self.size


def _bits_to_bytes(bits) -> bytes:
    out = bytearray((len(bits) + 7) // 8)
    for i, b in enumerate(bits):
        if b:
            out[i // 8] |= 1 << (i % 8)
    return bytes(out)


class Bitvector:
    basic = False

    def __init__(self, n):
        self.n = n

    def fixed(self):
        return (self.n + 7) // 8

    def decode(self, data):
        assert len(data) == self.fixed()
        bits = [bool(data[i // 8] >> (i % 8) & 1) for i in range(self.n)]
        assert not any(data[i // 8] >> (i % 8) & 1
                       for i in range(self.n, 8 * len(data)))
        return bits

    def root(self, v):
        assert len(v) == self.n
        return merkleize(_bits_to_bytes(v), (self.n + 255) // 256)


class Bitlist:
    basic = False

    def __init__(self, limit):
        self.limit = limit

    def fixed(self):
        return None

    def decode(self, data):
        assert len(data) > 0 and data[-1] != 0
        n = 8 * (len(data) - 1) + data[-1].bit_length() - 1
        assert n <= self.limit
        return [bool(data[i // 8] >> (i % 8) & 1) for i in range(n)]

    def root(self, v):
        return mix_in_length(
            merkleize(_bits_to_bytes(v), (self.limit + 255) // 256), len(v))


class Container:
    basic = False

    def __init__(self, name, fields):
        self.name, self.fields = name, fields

    def fixed(self):
        total = 0
        for _, t in self.fields:
            s = t.fixed()
            if s is None:
                return None
            total += s
        return total

    def decode(self, data):
        pos, out, var = 0, {}, []
        for name, t in self.fields:
            s = t.fixed()
            if s is None:
                var.append((name, t, struct.unpack_from("<I", data, pos)[0]))
                pos += 4
            else:
                out[name] = t.decode(data[pos:pos + s])
                pos += s
        if not var:
            assert pos == len(data), (self.name, pos, len(data))
            return out
        assert var[0][2] == pos, (self.name, var[0][2], pos)
        ends = [o for _, _, o in var[1:]] + [len(data)]
        for (name, t, start), end in zip(var, ends):
            assert start <= end <= len(data)
            out[name] = t.decode(data[start:end])
        return out

    def root(self, v):
        return merkleize(b"".join(t.root(v[name]) for name, t in self.fields),
                         len(self.fields))
