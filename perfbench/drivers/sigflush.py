"""Driver for signature-flush traffic: a block's signature sets, flushed
through ``bls.DeferredBatch`` (the call ``process_block`` makes) in a
closed loop, one flush after another.

Generation (from the configuration, the mix and ``--seed``): the
registry is ``distinct_keys`` secret keys 1..n (``test_infra/keys.py``'s
keys), their pubkeys computed by the plain reference.  Each flush holds
``max_attestations`` aggregates of ``committee_width`` distinct members
(seeded permutations of the registry, so every key serves in
``max_attestations * committee_width / distinct_keys`` committees), one
aggregate of ``sync_committee_size`` members, and ``singles``
one-key sets of the proposer.  Every set signs its own seeded 32-byte
message with one native ``Sign`` under the members' summed secret key,
so no signature repeats within a run, and every set is valid.

Correctness: the last program of every RLC flush,
``ops/jax_bls/pairing.staged_product_pairing_check``, receives the
product's pairs ``([r_i] agg_pk_i, H(m_i))`` and ``(-G1, sum_i [r_i]
sig_i)``.  The driver keeps those inputs; after the window it decodes
them for a seeded sample of flushes and compares every point with the
plain reference (``reference/rlc.py``), and every flush's verdict with
the reference's validity of its sets.  Since every set is valid, a
verdict cannot tell a pairing that always answers True: so the same
pairing program is then fed each sampled flush's pairs with one seeded
pair moved off by ``G2``, and has to answer False.
"""
import concurrent.futures
import contextlib
import hashlib
import re
import time
from dataclasses import dataclass, field

from reference import bls12_381 as RB
from reference import rlc as RLC

FALLBACK_KEY = re.compile(r"\.fallbacks(\{|$)|^bls\.flush\{.*path=fallback")
SIGN_THREADS = 4        # native Sign calls in parallel during set-up
CHECKED_FLUSHES = 2     # flushes the output check compares point by point


def seeded(seed, *tags) -> int:
    """A 64-bit integer from the seed and tags (any int seed works)."""
    h = hashlib.sha256(repr((int(seed),) + tags).encode()).digest()
    return int.from_bytes(h[:8], "little")


@dataclass
class Flush:
    items: list                 # [(pubkeys, message, signature)]
    secret_sums: list           # per item: sum of members' secret keys


@dataclass
class Op:
    index: int                  # position in the window
    flush: int                  # pool index
    t0: float
    t1: float
    units: int                  # signature sets
    ok: bool
    results: list               # per item verdicts the flush reported
    fallbacks: dict
    failed: bool                # a False verdict, or a fallback
    host_s: float = None        # flush start to its first device program
    spans: dict = field(default_factory=dict)


@dataclass
class Work:
    cfg: dict
    mix: dict
    seed: int
    pubkeys: list
    pool: list
    warm: Flush
    captured: dict = field(default_factory=dict)    # op index -> [args]
    repeats: int = 0


def registry_pubkeys(n):
    """Compressed pubkeys of secret keys 1..n: [k]G1 by repeated adds."""
    g = RB.from_affine(RB.Fp, RB.G1)
    acc, out = None, []
    for _ in range(n):
        acc = RB.add(RB.Fp, acc, g)
        out.append(RB.encode_g1(RB.affine(RB.Fp, acc)))
    return out


def _members(cfg, rng):
    """Committee member lists (indices into the registry) of one flush."""
    n = cfg["distinct_keys"]
    width = cfg["committee_width"]
    n_agg = cfg["max_attestations"]
    slots = []
    while len(slots) < n_agg * width:
        slots.extend(rng.permutation(n).tolist())
    out = [slots[a * width:(a + 1) * width] for a in range(n_agg)]
    out.append(rng.choice(n, cfg["sync_committee_size"],
                          replace=False).tolist())
    proposer = int(rng.integers(n))
    out.extend([[proposer]] * cfg["singles"])
    return out


def _make_flush(cfg, seed, f, pubkeys, signer):
    import numpy as np
    rng = np.random.default_rng(seeded(seed, "sigflush", f))
    members = _members(cfg, rng)
    msgs = [rng.bytes(32) for _ in members]
    sums = [sum(i + 1 for i in m) % RB.R for m in members]
    sigs = signer(sums, msgs)
    items = [([pubkeys[i] for i in m], msg, sig)
             for m, msg, sig in zip(members, msgs, sigs)]
    return Flush(items, sums)


def build(cfg, mix, seed) -> Work:
    """All inputs of one run, from the seed alone."""
    from consensus_specs_tpu.ops import native_bls
    if not native_bls.available():
        raise RuntimeError("native BLS library unavailable for signing")
    pubkeys = registry_pubkeys(cfg["distinct_keys"])
    with concurrent.futures.ThreadPoolExecutor(SIGN_THREADS) as ex:
        def signer(sums, msgs):
            return list(ex.map(native_bls.Sign, sums, msgs))
        pool = [_make_flush(cfg, seed, f, pubkeys, signer)
                for f in range(mix["pool_flushes"])]
        warm = _make_flush(cfg, seed, "warm", pubkeys, signer)
    return Work(cfg, mix, seed, pubkeys, pool, warm)


def _flush(fl):
    from consensus_specs_tpu.utils import bls
    bls.clear_verify_memo()
    batch = bls.DeferredBatch()
    for pks, msg, sig in fl.items:
        batch.add(pks, msg, sig)
    ok = batch.flush()
    return ok, batch.last_results


def warm(work):
    """The program path the window drives, at the window's shapes:
    ``use_fastest`` must pick the device backend."""
    from consensus_specs_tpu.utils import bls
    bls.bls_active = True
    bls.use_fastest()
    if bls.backend_name() != "jax":
        raise RuntimeError(f"use_fastest picked {bls.backend_name()!r}")
    ok, results = _flush(work.warm)
    if not all(results):
        raise RuntimeError("warm-up flush answered "
                           f"{[i for i, r in enumerate(results) if not r]} "
                           "invalid")


@contextlib.contextmanager
def capture(work):
    """Keep the inputs of the RLC product pairing, per window op, and the
    time a flush reaches its first device program (the pubkey tree sum):
    what comes before it is host work."""
    from consensus_specs_tpu.ops import bls_jax
    from consensus_specs_tpu.ops.jax_bls import pairing as PR
    orig = PR.staged_product_pairing_check
    orig_tree_sum = bls_jax._j_tree_sum
    current = {"op": None, "t_dev": None}

    def recorded(*args):
        if current["op"] is not None:
            work.captured.setdefault(current["op"], []).append(args)
        return orig(*args)

    def tree_sum(*args):
        current["t_dev"] = current["t_dev"] or time.perf_counter()
        return orig_tree_sum(*args)

    PR.staged_product_pairing_check = recorded
    bls_jax._j_tree_sum = tree_sum
    try:
        yield current
    finally:
        PR.staged_product_pairing_check = orig
        bls_jax._j_tree_sum = orig_tree_sum


def window(work, seconds, tracer, span_stats=None):
    """Flush back to back until ``seconds`` have passed; the window
    closes at the end of the first flush to finish after that.
    ``tracer.around(i, name)`` brackets op ``i``; ``span_stats``, when
    given, is read around every op."""
    from consensus_specs_tpu.obs import registry
    ops = []
    with capture(work) as current:
        t_open = time.perf_counter()
        i = 0
        while True:
            f = i % len(work.pool)
            fl = work.pool[f]
            current["op"], current["t_dev"] = i, None
            c0 = registry.counter_values()
            s0 = span_stats() if span_stats else None
            t0 = time.perf_counter()
            with tracer.around(i, "bench.flush"):
                ok, results = _flush(fl)
            t1 = time.perf_counter()
            c1 = registry.counter_values()
            fb = {k: v - c0.get(k, 0) for k, v in c1.items()
                  if v != c0.get(k, 0) and FALLBACK_KEY.search(k)}
            op = Op(i, f, t0, t1, len(fl.items), bool(ok), list(results), fb,
                    not ok or bool(fb))
            if current["t_dev"]:
                op.host_s = current["t_dev"] - t0
            if span_stats:
                s1 = span_stats()
                op.spans = {k: v - s0.get(k, 0.0) for k, v in s1.items()}
            ops.append(op)
            i += 1
            if t1 - t_open >= seconds:
                break
        current["op"] = None
    work.repeats = max(0, len(ops) - len(work.pool))
    return t_open, ops


# ---------------------------------------------------------------------------
# Correctness: the timed path's own RLC inputs against the reference
# ---------------------------------------------------------------------------

_R_INV = pow((1 << 384) % RB.P, -1, RB.P)


def _fp(limbs) -> int:
    """(24,) little-endian 16-bit Montgomery limbs -> canonical int."""
    v = 0
    for i, limb in enumerate(limbs):
        v |= int(limb) << (16 * i)
    return v * _R_INV % RB.P


def decode_pairs(args):
    """Captured ``(px, py, q, degenerate)`` -> per pair ``(g1, g2)``
    affine ints, ``None`` for a degenerate pair."""
    import numpy as np
    px, py, ((qx0, qx1), (qy0, qy1)), degen = args
    px, py, qx0, qx1, qy0, qy1, degen = map(
        np.asarray, (px, py, qx0, qx1, qy0, qy1, degen))
    out = []
    for i in range(px.shape[0]):
        if bool(degen[i]):
            out.append(None)
            continue
        out.append(((_fp(px[i]), _fp(py[i])),
                    ((_fp(qx0[i]), _fp(qx1[i])), (_fp(qy0[i]), _fp(qy1[i])))))
    return out


def expected_pairs(ref):
    """The pairs the RLC product should hold, from the reference fold."""
    neg_g1 = (RB.G1[0], RB.P - RB.G1[1])
    pairs = [None if a is None or h is None else (a, h)
             for a, h in zip(ref["agg"], ref["h"])]
    pairs.append(None if ref["sig_sum"] is None
                 else (neg_g1, ref["sig_sum"]))
    return pairs


def count_mismatches(got, want) -> int:
    """Pairs that differ, are missing or are extra."""
    wrong = abs(len(got) - len(want))
    return wrong + sum(1 for g, w in zip(got, want) if g != w)


def sample(work, ops):
    """Seeded sample of completed flushes, the first one always in it."""
    import numpy as np
    k = min(CHECKED_FLUSHES, len(ops))
    rng = np.random.default_rng(seeded(work.seed, "sample"))
    rest = rng.permutation(np.arange(1, len(ops)))[:k - 1].tolist()
    return [0] + sorted(rest)


def check(work, ops):
    """Numbers compared, each with its limit: ``(name, value, limit)``.

    ``outputs_wrong``, an exact count: over the sampled flushes, pairs of
    the product pairing whose points differ from the reference's, are
    missing or extra; plus per-set and per-flush verdicts that differ
    from the reference's validity of the sets (sampled flushes) or from
    the generator's, all valid (the rest); plus a True answer of the
    pairing to a sampled flush's pairs with one pair moved.
    """
    pubkeys = RLC.PubkeyCache()
    wrong = 0
    checked = sample(work, ops)
    for i in checked:
        fl = work.pool[ops[i].flush]
        ref = RLC.fold(fl.items, fl.secret_sums, pubkeys,
                       work.cfg["rlc_scalar_bits"])
        calls = work.captured.get(i, [])
        want = expected_pairs(ref)
        if len(calls) != 1:
            wrong += len(want) * max(1, len(calls))
        else:
            wrong += count_mismatches(decode_pairs(calls[0]), want)
            wrong += bool(pairing_accepts_moved_pair(work.seed, i, calls[0]))
        wrong += _verdicts_wrong(ops[i], ref["valid"])
    wrong += sum(_verdicts_wrong(op, [True] * op.units)
                 for i, op in enumerate(ops) if i not in checked)
    return [("outputs_wrong", wrong, 0)]


def pairing_accepts_moved_pair(seed, op_index, args):
    """The program's own product pairing, at the flush's shape, fed the
    flush's captured pairs with one seeded non-degenerate pair's G2 point
    moved by the generator: the product gains ``e(P_j, G2) != 1``, so a
    sound pairing answers False.  Returns the pairing's answer."""
    import numpy as np
    import jax.numpy as jnp
    from consensus_specs_tpu.ops.jax_bls import pairing as PR
    pairs = decode_pairs(args)
    live = [j for j, p in enumerate(pairs) if p is not None]
    rng = np.random.default_rng(seeded(seed, "moved pair", op_index))
    j = live[int(rng.integers(len(live)))]
    g2 = RB.from_affine(RB.Fp2, RB.G2)
    qj = RB.from_affine(RB.Fp2, pairs[j][1])
    # Q_j + G2, or Q_j + 2 G2 where Q_j = -G2 would leave the identity
    moved = (RB.affine(RB.Fp2, RB.add(RB.Fp2, qj, g2))
             or RB.affine(RB.Fp2, RB.add(RB.Fp2, qj, RB.dbl(RB.Fp2, g2))))
    px, py, ((qx0, qx1), (qy0, qy1)), degen = args
    q = [np.array(a) for a in (qx0, qx1, qy0, qy1)]
    for a, v in zip(q, (moved[0][0], moved[0][1], moved[1][0], moved[1][1])):
        a[j] = _limbs(v)
    px, py, degen, *q = (jnp.asarray(a) for a in (px, py, degen, *q))
    return bool(np.asarray(PR.staged_product_pairing_check(
        px, py, ((q[0], q[1]), (q[2], q[3])), degen)))


def _verdicts_wrong(op, truth):
    wrong = abs(len(op.results) - len(truth))
    wrong += sum(1 for a, b in zip(op.results, truth) if bool(a) != b)
    return wrong + (op.ok != all(truth))


# ---------------------------------------------------------------------------
# The control: the reference in the program's place, with the RLC scalars
# drawn at 64 bits instead of the 128 the configuration's batch-soundness
# guarantee states
# ---------------------------------------------------------------------------

_R_MONT = (1 << 384) % RB.P


def _limbs(v):
    m = v * _R_MONT % RB.P
    return [(m >> (16 * i)) & 0xFFFF for i in range(24)]


def encode_pairs(pairs):
    """Per pair ``(g1, g2)`` affine ints (None: degenerate) -> the
    ``(px, py, q, degenerate)`` arguments the program's pairing takes."""
    import numpy as np
    zero = ((0, 0), ((0, 0), (0, 0)))
    rows = [p or zero for p in pairs]

    def col(f):
        return np.array([_limbs(f(r)) for r in rows], dtype=np.uint32)
    return (col(lambda r: r[0][0]), col(lambda r: r[0][1]),
            ((col(lambda r: r[1][0][0]), col(lambda r: r[1][0][1])),
             (col(lambda r: r[1][1][0]), col(lambda r: r[1][1][1]))),
            np.array([p is None for p in pairs]))


def control_check(work, scalar_bits=64):
    """The check's numbers for the control, over as many flushes as a
    run checks."""
    pubkeys = RLC.PubkeyCache()
    ops = []
    for f in range(CHECKED_FLUSHES):
        fl = work.pool[f]
        ref = RLC.fold(fl.items, fl.secret_sums, pubkeys, scalar_bits)
        work.captured[f] = [encode_pairs(expected_pairs(ref))]
        ok = all(ref["valid"])
        ops.append(Op(f, f, 0.0, 0.0, len(fl.items), ok, list(ref["valid"]),
                      {}, False))
    return check(work, ops)
