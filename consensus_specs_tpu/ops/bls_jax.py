"""JAX/TPU BLS backend - the ``bls.use_jax()`` implementation.

Plays the role the milagro/arkworks Rust backends play in the reference
(``tests/core/pyspec/eth2spec/utils/bls.py:22-47``), but as batched XLA
programs: pubkey aggregation is a vectorized tree reduction, hash-to-curve
and the 2-pair product pairing run as one jitted kernel, and a whole
block's worth of aggregate verifications dispatches as a single batch
(``verify_aggregates_batch``).

Division of labor:

- Hot verification paths (``Verify``, ``FastAggregateVerify``,
  ``AggregateVerify`` and their batch forms) run on device.
- Cold/setup paths (``Sign``, ``SkToPk``, ``Aggregate``, ``AggregatePKs``,
  ``KeyValidate``) delegate to the pure-python oracle - same split as the
  reference's ``fastest_bls`` which mixes backends per function
  (``bls.py:35-47``).

Shape discipline: batch and aggregate axes are padded to powers of two so
the number of compiled program variants stays O(log n); padding lanes are
degenerate pairs that contribute the identity to the pairing product.
"""
import threading
from collections import OrderedDict
from typing import NamedTuple

import numpy as np
import jax  # tree_util; array ops ride the kernel backend switch
from consensus_specs_tpu.ops.jax_bls.backend import (
    xp as jnp, kjit, NUMPY_KERNELS)

from consensus_specs_tpu.ops.bls12_381 import ciphersuite as _oracle
from consensus_specs_tpu.obs import registry, tracing
from consensus_specs_tpu.obs.tracing import span
from consensus_specs_tpu.ops.bls12_381.curve import (
    G1Point, G2Point, G1_GENERATOR, g1_from_compressed, g2_from_compressed)
from consensus_specs_tpu.ops.jax_bls import limbs as L
from consensus_specs_tpu.ops.jax_bls import points as PT
from consensus_specs_tpu.ops.jax_bls import pairing as PR
from consensus_specs_tpu.ops.jax_bls import htc as HTC
from consensus_specs_tpu.utils import env_flags


def _profile_sync(tree):
    """Drain device work at a stage boundary, but ONLY while profiling —
    unconditional blocking would serialize the async dispatch pipeline
    the staged TPU path relies on."""
    if tracing.is_enabled():
        jax.block_until_ready(tree)

# Cold-path delegation (oracle)
Sign = _oracle.Sign
SkToPk = _oracle.SkToPk
Aggregate = _oracle.Aggregate
AggregatePKs = _oracle.AggregatePKs
KeyValidate = _oracle.KeyValidate

# ---------------------------------------------------------------------------
# Host-side decompression caches.  Pubkeys repeat across blocks/epochs (the
# validator registry), so decompression + subgroup checking is amortized -
# the reference gets the same effect from LRU caches around bytes48_to_G1.
# ---------------------------------------------------------------------------

class _LRU(OrderedDict):
    """Tiny bounded cache (reference analog: the C lru-dict the spec builder
    injects, ``pysetup/spec_builders/phase0.py:47-105``)."""

    def __init__(self, maxsize):
        super().__init__()
        self.maxsize = maxsize

    def put(self, key, value):
        self[key] = value
        self.move_to_end(key)
        if len(self) > self.maxsize:
            self.popitem(last=False)


# Pubkey-table rows written: cache misses that packed a key's row.  Zero
# in a steady window, where the warm-up already packed every key.
_PK_TABLE_ROWS = registry.counter("bls.pk_table.rows_added").labels()
_Z_ROWS = np.stack([L.ZERO, L.ONE_M])      # z of slot 0, z of a live slot


class _PubkeyCache(_LRU):
    """bytes48 -> ``[G1Point|None, slot|None]``: the oracle point, and
    the key's row in ONE contiguous table of packed affine Montgomery
    limbs (``x``, ``y``: (capacity, 24) uint32).  A flush gathers its
    (lanes x width) pubkey grid from the table with one fancy index per
    coordinate (:meth:`grid`), not a stack of a row pair per key.

    Slot 0 is the identity (x 0, y mont(1), z 0); padding lanes and keys
    index it.  A valid key gets a slot the first time it is packed (its
    one int->limb conversion for the cache's lifetime), keys that fail
    KeyValidate never get one, and an evicted key's slot goes back on
    the free list.  Capacity doubles as keys arrive, up to
    ``maxsize + 1``.  Hits are lock-free; admission, packing and
    eviction hold ``_lock`` (the serving pipeline flushes on a worker
    thread)."""

    def __init__(self, maxsize, capacity=1 << 10):
        super().__init__(maxsize)
        self._lock = threading.Lock()
        self._free = []
        self._next = 1
        self.x = np.zeros((min(capacity, maxsize + 1), L.NLIMB), np.uint32)
        self.y = np.zeros_like(self.x)
        self.y[0] = L.ONE_M

    def popitem(self, last=True):
        key, entry = super().popitem(last)
        if entry[1] is not None:
            self._free.append(entry[1])
            entry[1] = None     # re-admitted, the entry packs anew
        return key, entry

    def admit(self, key, entry):
        """Insert ``entry`` unless ``key`` is resident; the resident
        entry either way."""
        with self._lock:
            cur = self.get(key)
            if cur is None:
                self.put(key, entry)
                cur = entry
            return cur

    def pack(self, key, entry) -> int:
        """The slot of a valid key, its row written on first use."""
        with self._lock:
            cur = self.get(key)
            if cur is None:             # evicted since it was looked up
                self.put(key, entry)
            elif cur is not entry:      # re-admitted by another thread
                entry = cur
            if entry[1] is None:
                if self._free:
                    slot = self._free.pop()
                else:
                    slot = self._next
                    self._next += 1
                    if slot == len(self.x):
                        self._grow()
                pt = entry[0]
                self.x[slot] = L.fq_const(pt.x.n)
                self.y[slot] = L.fq_const(pt.y.n)
                entry[1] = slot
                _PK_TABLE_ROWS.add()
            return entry[1]

    def _grow(self):
        cap = min(2 * len(self.x), self.maxsize + 1)
        for name in ("x", "y"):
            old = getattr(self, name)
            new = np.zeros((cap, L.NLIMB), np.uint32)
            new[:len(old)] = old
            setattr(self, name, new)

    def grid(self, slot_rows, lanes: int, width: int) -> tuple:
        """Per-lane slot lists -> the (lanes, width) projective pytree
        ((lanes, width, 24) uint32 x3) of their points, every lane
        identity-padded: live (x, y, mont(1)), slot 0 (0, mont(1), 0) —
        ``PT.g1_pack``'s encoding."""
        idx = np.zeros((lanes, width), np.int32)
        for i, row in enumerate(slot_rows):
            idx[i, :len(row)] = row
        return (np.take(self.x, idx, axis=0), np.take(self.y, idx, axis=0),
                np.take(_Z_ROWS, np.minimum(idx, 1), axis=0))


# Pubkeys are bounded by the validator registry; signatures are unique per
# message so their cache mainly serves immediate re-verification.  One
# cache entry per pubkey holds BOTH views — the oracle G1Point and its
# slot in the packed table the device path gathers from — so the
# registry is never resident twice.
_g1_cache = _PubkeyCache(1 << 21)
_g2_cache = _LRU(1 << 14)


def _native():
    """The native C library when it is built, else None: it runs the
    subgroup checks that cost the python oracle ~17 ms per pubkey
    (8192 distinct keys = 2+ minutes of host time per cold block)."""
    from consensus_specs_tpu.ops import native_bls
    return native_bls if native_bls.available() else None


def _g1_entry(data: bytes):
    """bytes48 -> [G1Point|None, slot|None] (KeyValidate semantics:
    non-canonical, off-curve, out-of-subgroup and identity are None),
    touched as the cache's most recent entry."""
    key = bytes(data)
    entry = _g1_cache.get(key)
    if entry is not None:
        try:
            _g1_cache.move_to_end(key)
            return entry
        except KeyError:        # evicted by another thread meanwhile
            return _g1_cache.admit(key, entry)
    try:
        pt = g1_from_compressed(key)
        nb = _native()
        ok = (not pt.infinity) and (
            nb.KeyValidate(key) if nb else pt.in_subgroup())
        entry = [pt if ok else None, None]
    except Exception:
        entry = [None, None]
    return _g1_cache.admit(key, entry)


def _decompress_g1(data: bytes):
    """bytes48 -> G1Point or None if invalid per KeyValidate."""
    return _g1_entry(data)[0]


def _decompress_g2(data: bytes):
    """bytes96 -> G2Point (subgroup-checked; infinity allowed - the pairing
    handles it as a degenerate pair) or None if invalid."""
    key = bytes(data)
    if key not in _g2_cache:
        try:
            pt = g2_from_compressed(key)
            nb = _native()
            ok = nb.g2_validate(key) if nb else pt.in_subgroup()
            _g2_cache.put(key, pt if ok else None)
        except Exception:
            _g2_cache.put(key, None)
    return _g2_cache[key]


def _g1_slot(data: bytes):
    """bytes48 -> its pubkey-table slot, or None if the key fails
    KeyValidate.  The python int->limb conversion costs ~50us/point and
    registry pubkeys repeat across every block, so a key's row is packed
    once and stays in the table while its entry is cached."""
    entry = _g1_entry(data)
    if entry[0] is None:
        return None
    slot = entry[1]
    return slot if slot is not None else _g1_cache.pack(bytes(data), entry)


def _slot_rows(pubkey_lists) -> list:
    """Per item, its keys' pubkey-table slots, or None where a key fails
    KeyValidate.  Each key is touched as the cache's most recent, so no
    key of the batch is evicted (and its slot handed on) before the
    batch's grid is gathered — unless the batch alone carries more keys
    than the cache holds, which raises."""
    if sum(map(len, pubkey_lists)) > _g1_cache.maxsize:
        raise ValueError("a batch carries more pubkeys than the pubkey "
                         f"cache holds ({_g1_cache.maxsize})")
    out = []
    for pubkeys in pubkey_lists:
        slots = [_g1_slot(pk) for pk in pubkeys]
        out.append(None if None in slots else slots)
    return out


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


_NEG_G1 = PT.g1_pack([-G1_GENERATOR])

# Lanes per chunk of the per-lane verification path (the bisect behind a
# failed RLC flush, AggregateVerify, lone Verify calls).  On an
# accelerator one chunk takes the whole batch, up to 256 lanes: the
# compile time of a staged program barely depends on its lane count
# (v5e AOT, PR 21: 5-30 s per program at 8, 32 or 256 lanes alike), and
# a batch padded to its own lane bucket reuses the aggregation and
# hash-to-curve programs the RLC flush of the same block compiled (131
# items -> 256 lanes: 12 new programs for the bisect instead of 32).
# 256 x 512-key rows of the pubkey tree sum hold 3.8 GB of one v5e's
# 16 GB (AOT for a described v5e: 3.6 GiB of temporaries, the rest its
# arguments).  On the host CPU compile time dominates, so chunks stay at
# 8 lanes.  ``CS_TPU_BLS_BATCH`` overrides.
def bucket_b() -> int:
    """Most lanes per chunk; resolved at first dispatch (asking JAX for
    its backend initializes it, which importing this module must not)."""
    global _BUCKET_B
    if _BUCKET_B is None:
        raw = env_flags.knob("CS_TPU_BLS_BATCH")
        if raw is not None:
            _BUCKET_B = int(raw)
        elif NUMPY_KERNELS or jax.default_backend() == "cpu":
            _BUCKET_B = 8
        else:
            _BUCKET_B = 256
    return _BUCKET_B


def _chunk_lanes(n_rows: int) -> int:
    """Lanes per chunk for ``n_rows`` rows: their own lane bucket, at
    most :func:`bucket_b`."""
    return min(bucket_b(), max(PR.LANE_BUCKET, _pow2(n_rows)))


_BUCKET_B = None
# Pubkey-aggregation axis buckets (the aggregate program is cheap to
# compile, so power-of-two buckets with a floor are fine).
_N_MIN = 8

# ---------------------------------------------------------------------------
# Device programs (jitted once per shape bucket)
# ---------------------------------------------------------------------------

@kjit
def _j_tree_sum(pk_pts):
    """(B, N) projective G1 pytree -> (B,) unnormalized sum; one halving
    tree program per (B, N) bucket."""
    return PT.g1_tree_sum_batched(pk_pts)


def _j_g1_normalize_flag(p):
    """Normalize + identity flag; the inversion rides the shared ladder
    program (round-4 compile-cost restructuring)."""
    return PT.g1_normalize_flag_staged(p)


def _program_aggregate(pk_pts):
    """(B, N) projective G1 pytree -> normalized (B,) aggregate + inf
    flag, as bounded programs (sum, then staged normalize).

    The batch axis pads to the shared lane bucket (identity rows) so the
    bench / graft-entry / dryrun consumers hit the same tree-sum and
    normalize compiles."""
    b = pk_pts[0].shape[0]
    bucket = PR.lane_bucket(b)
    if bucket != b:
        from consensus_specs_tpu.ops.jax_bls.limbs import ZERO, ONE_M
        n = bucket - b
        pk_pts = (PR.pad_axis(pk_pts[0], 0, n, ZERO),
                  PR.pad_axis(pk_pts[1], 0, n, ONE_M),
                  PR.pad_axis(pk_pts[2], 0, n, ZERO))
    agg, inf = _j_g1_normalize_flag(_j_tree_sum(pk_pts))
    if bucket != b:
        agg = jax.tree_util.tree_map(lambda a: a[:b], agg)
        inf = inf[:b]
    return agg, inf


def _program_g2_normalize(p):
    return PT.g2_normalize_staged(p)


def _program_htc(u0, u1):
    """hash_to_field outputs -> affine G2 points (B,).

    Staged dispatch (sswu+iso stacked, add+cofactor, normalize): the
    monolithic module compiles pathologically slowly on XLA:CPU; the
    stages are each bounded and individually cached.  The batch axis is
    padded to the shared lane bucket so every consumer hits one set of
    compiled SSWU/ladder programs (see pairing.staged_pairing_check)."""
    b = u0[0].shape[0]
    bucket = PR.lane_bucket(b)
    if bucket != b:
        pad = lambda a: PR.pad_axis(a, 0, bucket - b)
        u0 = (pad(u0[0]), pad(u0[1]))
        u1 = (pad(u1[0]), pad(u1[1]))
    out = _program_g2_normalize(HTC.map_to_g2_staged(u0, u1))
    if bucket != b:
        out = jax.tree_util.tree_map(lambda a: a[:b], out)
    return out


@kjit
def _program_multi_pair_verify(px, py, qx0, qx1, qy0, qy1, degen):
    """Batched n-pair product pairing check as ONE program: (B, n_pairs,
    ...) inputs.  The graft entry's flagship compile check
    (``__graft_entry__.entry``); the verification paths dispatch the
    same math as the staged pipeline (``PR.staged_pairing_check``)."""
    def one(px, py, a, b, c, d, dg):
        return PR.pairing_check(px, py, ((a, b), (c, d)), dg)
    return jax.vmap(one)(px, py, qx0, qx1, qy0, qy1, degen)


def _program_agg_verify(pk_pts, u0, u1, sig_q, agg_degen, sig_degen):
    """Batched FastAggregateVerify as a pipeline of bounded device
    programs: maximal cross-shape reuse — only the aggregation program
    depends on the per-aggregate pubkey count.  (A fused monolith of
    the same math took 1284 s to compile for a v5e at 32 lanes x 512
    keys, AOT, PR 21; the staged bisect behind Leg A's flush adds 12
    programs and 138 s to what the RLC flush already compiled.)
    """
    with span("bls.stage.aggregate"):
        agg, agg_inf = _program_aggregate(pk_pts)
        _profile_sync(agg)
    with span("bls.stage.htc"):
        hpt = _program_htc(u0, u1)
        _profile_sync(hpt)
    # assemble (pairs=2, B, ...) inputs for the staged pairing pipeline
    px = jnp.stack([agg[0], jnp.broadcast_to(_NEG_G1[0][0], agg[0].shape)])
    py = jnp.stack([agg[1], jnp.broadcast_to(_NEG_G1[1][0], agg[1].shape)])
    qx0 = jnp.stack([hpt[0][0], sig_q[0][0]])
    qx1 = jnp.stack([hpt[0][1], sig_q[0][1]])
    qy0 = jnp.stack([hpt[1][0], sig_q[1][0]])
    qy1 = jnp.stack([hpt[1][1], sig_q[1][1]])
    degen = jnp.stack([agg_degen | agg_inf, sig_degen])
    with span("bls.stage.pairing"):
        return np.asarray(PR.staged_pairing_check(
            px, py, ((qx0, qx1), (qy0, qy1)), degen))


# ---------------------------------------------------------------------------
# Batch API - the TPU-native entry points
# ---------------------------------------------------------------------------

def verify_aggregates_batch(items) -> list:
    """items: [(pubkeys: list[bytes48], message: bytes, signature: bytes96)].

    One device dispatch for the whole batch - this is what
    ``process_operations`` maps a block's 128 attestations onto.
    """
    if not items:
        return []
    with span("bls.verify_aggregates_batch"):
        return _verify_aggregates_batch(items)


def _verify_aggregates_batch(items) -> list:
    results_host = [None] * len(items)
    rows = []
    pk_slots = _slot_rows([pubkeys for pubkeys, _, _ in items])
    with span("bls.stage.sig_decompress"):
        sig_pts = [_decompress_g2(sig) for _, _, sig in items]
    for idx, ((pubkeys, msg, _), slots, spt) in enumerate(
            zip(items, pk_slots, sig_pts)):
        if len(pubkeys) == 0 or slots is None or spt is None:
            results_host[idx] = False
            continue
        rows.append((idx, slots, bytes(msg), spt))
    if not rows:
        return [bool(r) for r in results_host]

    B = _chunk_lanes(len(rows))
    # A lone wide aggregate (altair's 512-key sync committee) would pad
    # B-1 dead lanes through aggregation+hash-to-curve+pairing — an 8-16x
    # waste exactly where the work per lane is largest.  Give it a 1-lane
    # program set instead; the >=128 floor keeps small single verifies on
    # the shared lane bucket so this adds at most one extra compile set.
    if len(rows) == 1 and _pow2(len(rows[0][1])) >= 128:
        B = 1
    for start in range(0, len(rows), B):
        chunk = rows[start:start + B]
        n_pad = max(_N_MIN, _pow2(max(len(r[1]) for r in chunk)))
        sig_pts, msgs = [], []
        for _, _, msg, spt in chunk:
            sig_pts.append(spt)
            msgs.append(msg)
        for _ in range(B - len(chunk)):   # degenerate padding rows
            sig_pts.append(G2Point.inf())
            msgs.append(b"")

        with span("bls.stage.host_pack"):
            pk_pts = tuple(jnp.asarray(a) for a in _g1_cache.grid(
                [r[1] for r in chunk], B, n_pad))
        with span("bls.stage.hash_to_field"):
            u0, u1 = HTC.hash_to_field_host(msgs)
        sig_packed = PT.g2_pack(sig_pts)
        sig_q = (sig_packed[0], sig_packed[1])
        sig_degen = jnp.array([p.infinity for p in sig_pts])
        agg_degen = jnp.array(
            [False] * len(chunk) + [True] * (B - len(chunk)))

        out = np.asarray(_program_agg_verify(
            pk_pts, u0, u1, sig_q, agg_degen, sig_degen))
        for j, (idx, _, _, _) in enumerate(chunk):
            results_host[idx] = bool(out[j])
    return [bool(r) for r in results_host]


def aggregate_verify_batch(items) -> list:
    """items: [(pubkeys, messages, signature)] with distinct messages.

    Each item becomes n+1 pairs: (pk_i, H(m_i)) ... (-G1, sig), padded to a
    power of two with degenerate pairs.
    """
    if not items:
        return []
    results_host = [None] * len(items)
    rows = []
    for idx, (pubkeys, messages, sig) in enumerate(items):
        pts = [_decompress_g1(pk) for pk in pubkeys]
        spt = _decompress_g2(sig)
        if (len(pubkeys) == 0 or len(pubkeys) != len(messages)
                or any(p is None for p in pts) or spt is None):
            results_host[idx] = False
            continue
        rows.append((idx, pts, [bytes(m) for m in messages], spt))
    if not rows:
        return [bool(r) for r in results_host]

    B = _chunk_lanes(len(rows))
    for start in range(0, len(rows), B):
        chunk = rows[start:start + B]
        npair_pad = max(_N_MIN, _pow2(max(len(r[1]) for r in chunk) + 1))
        all_msgs, g1_rows, g2_sigs, degen_rows = [], [], [], []
        for _, pts, messages, spt in chunk:
            pad = npair_pad - 1 - len(pts)
            g1_rows.append(pts + [G1Point.inf()] * pad + [-G1_GENERATOR])
            all_msgs.extend(messages + [b""] * pad)
            g2_sigs.append(spt)
            degen_rows.append([False] * len(pts) + [True] * pad
                              + [spt.infinity])
        for _ in range(B - len(chunk)):
            g1_rows.append([G1Point.inf()] * npair_pad)
            all_msgs.extend([b""] * (npair_pad - 1))
            g2_sigs.append(G2Point.inf())
            degen_rows.append([True] * npair_pad)

        # hash all messages in one device call, scatter into (B, n-1) slots
        u0, u1 = HTC.hash_to_field_host(all_msgs)
        hpts = _program_g2_normalize(HTC._map_to_g2_jit(u0, u1))
        hx = ((hpts[0][0]).reshape(B, npair_pad - 1, 24),
              (hpts[0][1]).reshape(B, npair_pad - 1, 24))
        hy = ((hpts[1][0]).reshape(B, npair_pad - 1, 24),
              (hpts[1][1]).reshape(B, npair_pad - 1, 24))
        sig_packed = PT.g2_pack(g2_sigs)
        qx0 = jnp.concatenate([hx[0], sig_packed[0][0][:, None]], axis=1)
        qx1 = jnp.concatenate([hx[1], sig_packed[0][1][:, None]], axis=1)
        qy0 = jnp.concatenate([hy[0], sig_packed[1][0][:, None]], axis=1)
        qy1 = jnp.concatenate([hy[1], sig_packed[1][1][:, None]], axis=1)

        packed = PT.g1_pack([p for row in g1_rows for p in row])
        px = packed[0].reshape(B, npair_pad, 24)
        py = packed[1].reshape(B, npair_pad, 24)
        degen = jnp.array(degen_rows)
        # a G1 infinity in a live pair must also degenerate its pair
        inf_mask = np.array([[p.infinity for p in row] for row in g1_rows])
        degen = degen | jnp.asarray(inf_mask)

        mv = lambda a: jnp.moveaxis(a, 0, 1)   # (B, n_pairs) -> (n_pairs, B)
        out = np.asarray(PR.staged_pairing_check(
            mv(px), mv(py),
            ((mv(qx0), mv(qx1)), (mv(qy0), mv(qy1))), mv(degen)))
        for j, (idx, _, _, _) in enumerate(chunk):
            results_host[idx] = bool(out[j])
    return [bool(r) for r in results_host]


# ---------------------------------------------------------------------------
# RLC combined check - the one-pairing-per-block path.
#
# ``utils/bls.DeferredBatch.flush`` folds a whole block's queued
# FastAggregateVerify checks (plus any deferred raw pairing checks, e.g.
# the Deneb blob-KZG batch) into
#
#   prod_i e(r_i * agg_pk_i, H(m_i)) * e(-G1, sum_i r_i * sig_i) == 1
#
# so the device work is: one batched pubkey aggregation, one batched
# 128-bit G1 scaling, one G2 MSM over the signatures, hash-to-curve, and
# a SINGLE product pairing check (one final exponentiation) - versus one
# full 2-pair pairing check per lane on the per-lane path.  The pubkey
# aggregation and scaling, which read no signature, are their own
# phase, launched before the host decodes the signatures.
# ---------------------------------------------------------------------------

@kjit
def _j_g1_scale(pts, bits):
    """(B,) packed projective G1 x (B, n_bits) MSB-first bit planes ->
    (B,) scaled points (no reduction - per-lane [r_i]P_i)."""
    return PT.g1_scalar_mul(pts, bits)


@kjit
def _j_g2_scale_sum(sig_pts, bits):
    """(B,) packed projective G2 x (B, n_bits) bits -> sum_i [r_i]Q_i,
    the RLC signature MSM: per-lane double-and-add, log-depth tree sum."""
    return PT.g2_tree_sum(PT.g2_scalar_mul(sig_pts, bits))


def _bits_msb(scalars, n_bits: int) -> np.ndarray:
    """(B,) ints -> (B, n_bits) uint32 MSB-first bit planes.

    Vectorized via unpackbits over big-endian byte rows: this sits in
    the per-block host_pack stage, where a per-bit python loop
    (B x n_bits iterations) would be a fixed serial tax per flush."""
    n_bytes = (n_bits + 7) // 8
    rows = np.frombuffer(
        b"".join(int(s).to_bytes(n_bytes, "big") for s in scalars),
        dtype=np.uint8).reshape(len(scalars), n_bytes)
    bits = np.unpackbits(rows, axis=1)[:, -n_bits:]
    return bits.astype(np.uint32)


RLC_SCALAR_BITS = 128
# Pubkey rows of the RLC tree sum: ``live`` rows hold a key, ``dispatched``
# is the (lane bucket x padded width) grid the program adds up
_PK_ROWS_LIVE = registry.counter("bls.rlc.pk_rows").labels(kind="live")
_PK_ROWS_DISPATCHED = registry.counter("bls.rlc.pk_rows").labels(
    kind="dispatched")


class RlcPubkeySide(NamedTuple):
    """The in-flight device arrays of an RLC flush's pubkey phase
    (:func:`rlc_pubkey_phase`)."""
    n: int              # items
    bucket: int         # lanes the programs run
    bits: object        # (bucket, 128) MSB-first scalar bit planes
    aggp: tuple         # normalized [r_i] agg_pk_i, (bucket,)
    agg_inf: object     # their identity flags


def rlc_pubkey_phase(pk_slots, scalars):
    """Launch the part of an RLC flush that reads neither signature nor
    message: the pubkey tree sum, the 128-bit G1 scaling and its
    normalization, a few programs with most of the flush's device time.
    Returns the in-flight device arrays (an :class:`RlcPubkeySide`;
    None for no items) without waiting on the device, so the host
    decodes the signatures while they run.  Hash-to-curve stays out:
    launched here, its dozens of small programs queue behind the tree
    sum and hold the host until it is done (TPU v5e), which cancels
    the overlap.

    ``pk_slots``: per item, its pubkeys' slots in the pubkey table
    (``_slot_rows``: already KeyValidate-checked); ``scalars``: the
    per-item 128-bit RLC coefficients.
    """
    n = len(pk_slots)
    assert len(scalars) >= n
    if not n:
        return None
    bucket = PR.lane_bucket(n)
    npk_pad = max(_N_MIN, _pow2(max(len(r) for r in pk_slots)))
    pad_scalars = list(scalars[:n]) + [0] * (bucket - n)
    _PK_ROWS_LIVE.add(sum(len(r) for r in pk_slots))
    _PK_ROWS_DISPATCHED.add(bucket * npk_pad)

    with span("bls.stage.host_pack"):
        pk_pts = tuple(jnp.asarray(a) for a in _g1_cache.grid(
            pk_slots, bucket, npk_pad))
        bits = jnp.asarray(_bits_msb(pad_scalars, RLC_SCALAR_BITS))
    with span("bls.stage.msm"):
        # per-item aggregate, then the 128-bit scale
        agg = _j_tree_sum(pk_pts)
        aggp, agg_inf = _j_g1_normalize_flag(_j_g1_scale(agg, bits))
    return RlcPubkeySide(n, bucket, bits, aggp, agg_inf)


def rlc_signature_phase(pk_side, msgs, sig_pts, extra_pairs=(),
                        mesh_devices=None) -> bool:
    """Finish an RLC flush: the G2 MSM over the signatures,
    hash-to-curve of the messages, then the ONE product pairing over the
    item pairs, the folded signature pair and ``extra_pairs``.

    ``pk_side``: :func:`rlc_pubkey_phase`'s result; ``msgs``: per-item
    message bytes; ``sig_pts``: per-item oracle G2Points
    (subgroup-checked; infinity allowed); ``extra_pairs``: pre-scaled
    oracle ``(G1Point, G2Point)`` pairs appended to the product
    (deferred raw pairing checks, e.g. the blob-KZG batch).
    ``mesh_devices``: optional 1D device tuple - the signature MSM
    shards its point axis across it through
    ``parallel.sharded_verify.make_sharded_g2_msm``.
    """
    n = pk_side.n if pk_side else 0
    assert n == len(msgs) == len(sig_pts)
    px_parts, py_parts = [], []
    qx0_parts, qx1_parts, qy0_parts, qy1_parts = [], [], [], []
    degen_parts = []
    if n:
        bucket = pk_side.bucket
        with span("bls.stage.host_pack"):
            sig_packed = PT.g2_pack(list(sig_pts)
                                    + [G2Point.inf()] * (bucket - n))
        with span("bls.stage.msm"):
            # the G2 MSM (points-sharded when a mesh is registered;
            # uneven batches pad with identity lanes, so ANY bucket
            # size shards across ANY device count)
            if mesh_devices:
                from consensus_specs_tpu.parallel import sharded_verify
                s_total = sharded_verify.sharded_g2_msm_padded(
                    sig_packed, pk_side.bits, tuple(mesh_devices))
            else:
                s_total = _j_g2_scale_sum(sig_packed, pk_side.bits)
            s_total = jax.tree_util.tree_map(lambda a: a[None], s_total)
            s_aff = _program_g2_normalize(s_total)
            s_inf = jnp.asarray(PT.g2_is_identity(s_aff))

        with span("bls.stage.hash_to_field"):
            u0, u1 = HTC.hash_to_field_host(
                list(msgs) + [b""] * (bucket - n))
        with span("bls.stage.htc"):
            hpt = _program_htc(u0, u1)
            # a traced flush's one drain: here, after the signatures
            # were decoded, so tracing keeps the pubkey side's overlap
            _profile_sync((pk_side.aggp, s_aff, hpt))

    with span("bls.stage.assemble"):
        if n:
            aggp = pk_side.aggp
            # flat pairs axis: n item pairs + the folded signature pair
            px_parts += [aggp[0][:n], jnp.asarray(_NEG_G1[0])]
            py_parts += [aggp[1][:n], jnp.asarray(_NEG_G1[1])]
            qx0_parts += [hpt[0][0][:n], s_aff[0][0]]
            qx1_parts += [hpt[0][1][:n], s_aff[0][1]]
            qy0_parts += [hpt[1][0][:n], s_aff[1][0]]
            qy1_parts += [hpt[1][1][:n], s_aff[1][1]]
            degen_parts += [np.asarray(pk_side.agg_inf)[:n],
                            np.asarray(s_inf)]
        if extra_pairs:
            eg1 = PT.g1_pack([p for p, _ in extra_pairs])
            eg2 = PT.g2_pack([q for _, q in extra_pairs])
            px_parts.append(eg1[0]); py_parts.append(eg1[1])
            qx0_parts.append(eg2[0][0]); qx1_parts.append(eg2[0][1])
            qy0_parts.append(eg2[1][0]); qy1_parts.append(eg2[1][1])
            degen_parts.append(np.array(
                [p.infinity or q.infinity for p, q in extra_pairs]))
        cat = jnp.concatenate
        px = cat([jnp.asarray(a) for a in px_parts])
        py = cat([jnp.asarray(a) for a in py_parts])
        q = ((cat([jnp.asarray(a) for a in qx0_parts]),
              cat([jnp.asarray(a) for a in qx1_parts])),
             (cat([jnp.asarray(a) for a in qy0_parts]),
              cat([jnp.asarray(a) for a in qy1_parts])))
        degen = jnp.asarray(np.concatenate(degen_parts))

    with span("bls.stage.pairing"):
        return bool(np.asarray(
            PR.staged_product_pairing_check(px, py, q, degen)))


def rlc_combined_check(pk_slots, msgs, sig_pts, scalars, extra_pairs=(),
                       mesh_devices=None) -> bool:
    """One product pairing for a whole flushed batch:
    :func:`rlc_pubkey_phase` then :func:`rlc_signature_phase` (see them
    for the arguments).  ``bls_rlc`` calls the phases apart, decoding
    the signatures between them."""
    return rlc_signature_phase(rlc_pubkey_phase(pk_slots, scalars), msgs,
                               sig_pts, extra_pairs, mesh_devices)


# Public staged-program surface (the sharded step in
# consensus_specs_tpu.parallel and the dryrun's numpy cross-check both
# finish through this):
def verify_from_aggregate(total, u0, u1, sig_q, agg_degen, sig_degen):
    """Finish a batched FastAggregateVerify from an UNNORMALIZED projective
    aggregate: normalize, hash-to-curve, 2-pair product pairing check.

    This is the downstream half of the sharded step
    (``parallel.sharded_verify.make_sharded_agg_verify``) and of the
    multichip dryrun's numpy cross-check - one implementation, whichever
    process computed the aggregate."""
    aggp, agg_inf = _j_g1_normalize_flag(total)
    hpt = _program_htc(u0, u1)
    b = aggp[0].shape[:-1]
    px = jnp.stack([aggp[0], jnp.broadcast_to(_NEG_G1[0][0], b + (24,))])
    py = jnp.stack([aggp[1], jnp.broadcast_to(_NEG_G1[1][0], b + (24,))])
    qx = (jnp.stack([hpt[0][0], sig_q[0][0]]),
          jnp.stack([hpt[0][1], sig_q[0][1]]))
    qy = (jnp.stack([hpt[1][0], sig_q[1][0]]),
          jnp.stack([hpt[1][1], sig_q[1][1]]))
    degen = jnp.stack([agg_degen | agg_inf, sig_degen])
    return PR.staged_pairing_check(px, py, (qx, qy), degen)


def FastAggregateVerify(pubkeys, message: bytes, signature: bytes) -> bool:
    return verify_aggregates_batch([(pubkeys, message, signature)])[0]


def Verify(pubkey: bytes, message: bytes, signature: bytes) -> bool:
    return verify_aggregates_batch([([pubkey], message, signature)])[0]


def AggregateVerify(pubkeys, messages, signature: bytes) -> bool:
    # PoP ciphersuite: no distinct-message requirement (oracle parity,
    # ciphersuite.py AggregateVerify)
    return aggregate_verify_batch([(pubkeys, messages, signature)])[0]
