"""Random-linear-combination (RLC) batch-verification suite.

Adversarial soundness, per-item bisect reporting, memo interplay,
determinism, path counters, KZG-batch deferral, and the differential
guarantee that the RLC flush (``CS_TPU_BLS_RLC=1``, default), the
per-lane flush (``CS_TPU_BLS_RLC=0``) and the pure-python backend agree
item-for-item across every enqueue site (proposer signature, randao,
attestations, sync aggregate).  See ``docs/bls-batching.md``.
"""
import os

import pytest

from consensus_specs_tpu.ops import bls_rlc
from consensus_specs_tpu.ops.bls12_381.curve import (
    G1_GENERATOR, G2_GENERATOR, g2_from_compressed, msm)
from consensus_specs_tpu.obs import registry
from consensus_specs_tpu.utils import bls

MSG_A = b"\xab" * 32
MSG_B = b"\xcd" * 32
INF_PK = bytes([0xC0]) + b"\x00" * 47
INF_SIG = bytes([0xC0]) + b"\x00" * 95

_PAIRINGS = registry.counter("bls.pairings")
_FLUSH = registry.counter("bls.flush")
_HITS = registry.counter("cache.hit")


def setup_module():
    bls.use_py()
    bls.bls_active = True


def setup_function(_fn):
    bls.use_py()
    bls.clear_verify_memo()


class _rlc_env:
    """Temporarily force CS_TPU_BLS_RLC (the switch re-reads os.environ
    at flush time when the variable is present)."""

    def __init__(self, value):
        self.value = value

    def __enter__(self):
        self.old = os.environ.get("CS_TPU_BLS_RLC")
        os.environ["CS_TPU_BLS_RLC"] = self.value

    def __exit__(self, *exc):
        if self.old is None:
            del os.environ["CS_TPU_BLS_RLC"]
        else:
            os.environ["CS_TPU_BLS_RLC"] = self.old


def _sig_items(n=3):
    """n valid (pubkeys, msg, sig) items: one 3-key aggregate + singles."""
    pks = [bls.SkToPk(i) for i in (1, 2, 3)]
    agg = bls.Aggregate([bls.Sign(i, MSG_A) for i in (1, 2, 3)])
    items = [(pks, MSG_A, agg)]
    for i in range(4, 3 + n):
        m = bytes([i]) * 32
        items.append(([bls.SkToPk(i)], m, bls.Sign(i, m)))
    return items[:n]


def _flush_batch(items):
    """Queue items through the public API and flush; returns (ok, batch)."""
    with bls.batched_verification() as batch:
        for pks, msg, sig in items:
            if len(pks) == 1:
                bls.Verify(pks[0], msg, sig)
            else:
                bls.FastAggregateVerify(pks, msg, sig)
    ok = batch.flush()
    return ok, batch


# ---------------------------------------------------------------------------
# One pairing per block (counter-asserted) + path labels
# ---------------------------------------------------------------------------

def test_rlc_flush_is_one_pairing():
    items = _sig_items(3)
    with _rlc_env("1"):
        p0, r0 = _PAIRINGS.total(), _FLUSH.value(path="rlc")
        ok, _ = _flush_batch(items)
        assert ok
        assert _PAIRINGS.total() - p0 == 1
        assert _FLUSH.value(path="rlc") - r0 == 1


def test_rlc_disabled_runs_lane_path():
    items = _sig_items(2)
    with _rlc_env("0"):
        assert not bls.rlc_enabled()
        p0, l0 = _PAIRINGS.total(), _FLUSH.value(path="lanes")
        ok, _ = _flush_batch(items)
        assert ok
        assert _PAIRINGS.total() - p0 == len(items)
        assert _FLUSH.value(path="lanes") - l0 == 1


def test_combined_failure_falls_back_and_bisects():
    items = _sig_items(3)
    bad = ([bls.SkToPk(9)], MSG_B, bls.Sign(9, MSG_A))   # wrong message
    with _rlc_env("1"):
        f0 = _FLUSH.value(path="fallback", reason="bisect")
        ok, batch = _flush_batch(items + [bad])
        assert not ok
        assert batch.last_results == [True, True, True, False]
        assert _FLUSH.value(path="fallback", reason="bisect") - f0 == 1


def test_assert_valid_reports_failing_indices():
    bad = ([bls.SkToPk(9)], MSG_B, bls.Sign(9, MSG_A))
    with bls.batched_verification() as batch:
        bls.FastAggregateVerify(*_sig_items(1)[0])
        bls.Verify(bad[0][0], bad[1], bad[2])
    with pytest.raises(AssertionError, match=r"items \[1\]"):
        batch.assert_valid()


# ---------------------------------------------------------------------------
# Adversarial soundness
# ---------------------------------------------------------------------------

def test_forged_pair_whose_sum_verifies_is_rejected():
    """sig1' = sig1 + D, sig2' = sig2 - D: the *unrandomized* fold
    sum(sig_i') equals sum(sig_i) so a naive combined check would accept;
    the RLC coefficients kill the cancellation (r1*D != r2*D w.h.p.)."""
    pk1, pk2 = bls.SkToPk(11), bls.SkToPk(12)
    s1, s2 = bls.Sign(11, MSG_A), bls.Sign(12, MSG_B)
    D = g2_from_compressed(bls.Sign(99, b"delta"))
    f1 = (g2_from_compressed(s1) + D).to_compressed()
    f2 = (g2_from_compressed(s2) - D).to_compressed()
    # the attack premise holds: the sums agree...
    assert bls.Aggregate([f1, f2]) == bls.Aggregate([s1, s2])
    # ...but the RLC flush rejects, and the bisect blames both items
    ok, batch = _flush_batch([([pk1], MSG_A, f1), ([pk2], MSG_B, f2)])
    assert not ok
    assert batch.last_results == [False, False]


def test_mixed_structural_invalids_bisect_exactly():
    """Invalid encodings / infinity pubkey / empty pubkeys / infinity
    signature inside an otherwise-valid batch surface the right per-item
    verdicts through the fallback."""
    good = _sig_items(1)[0]
    items = [
        good,
        ([INF_PK], MSG_A, bls.Sign(1, MSG_A)),      # infinity pubkey
        ([], MSG_A, bls.Sign(1, MSG_A)),            # empty pubkey list
        ([bls.SkToPk(2)], MSG_A, b"\x00" * 96),     # malformed signature
        ([b"\xff" * 48], MSG_A, bls.Sign(2, MSG_A)),  # x >= p pubkey
        ([bls.SkToPk(3)], MSG_A, INF_SIG),          # infinity signature
        _sig_items(3)[2],
    ]
    ok, batch = _flush_batch(items)
    assert not ok
    assert batch.last_results == [True, False, False, False, False,
                                  False, True]


def test_infinity_signature_accepted_only_for_degenerate_claim():
    """An infinity signature is a *valid encoding* but only verifies when
    the whole claim is degenerate — it must not poison the batch."""
    good = _sig_items(1)[0]
    ok, batch = _flush_batch([good, ([bls.SkToPk(4)], MSG_A, INF_SIG)])
    assert not ok
    assert batch.last_results == [True, False]


# ---------------------------------------------------------------------------
# Deterministic seeding
# ---------------------------------------------------------------------------

def test_scalar_derivation_is_deterministic_and_input_sensitive():
    items = [([b"\x01" * 48], MSG_A, b"\x02" * 96),
             ([b"\x03" * 48], MSG_B, b"\x04" * 96)]
    a = bls_rlc.derive_scalars(items)
    b = bls_rlc.derive_scalars(items)
    assert a == b and len(a) == 2
    assert all(0 < r < (1 << bls_rlc.SCALAR_BITS) for r in a)
    # any queued byte changing re-randomizes every coefficient
    mutated = [(items[0][0], MSG_A, b"\x05" * 96), items[1]]
    c = bls_rlc.derive_scalars(mutated)
    assert a[0] != c[0] and a[1] != c[1]
    # extra checks draw their own coefficients after the items
    extra = [([(G1_GENERATOR, G2_GENERATOR)], "kzg_batch")]
    d = bls_rlc.derive_scalars(items, extra)
    assert len(d) == 3


def test_oracle_g2_msm_matches_naive():
    sigs = [g2_from_compressed(bls.Sign(i, bytes([i]) * 32))
            for i in (1, 2, 3)]
    rs = [5, (1 << 127) + 3, 12345678901234567890]
    got = msm(sigs, rs)
    exp = sigs[0].mult(rs[0]) + sigs[1].mult(rs[1]) + sigs[2].mult(rs[2])
    assert got == exp


# ---------------------------------------------------------------------------
# Memo interplay (satellite: check before enqueue, record at flush)
# ---------------------------------------------------------------------------

def test_replayed_batch_skips_device_work_via_memo():
    items = _sig_items(3)
    ok, _ = _flush_batch(items)
    assert ok
    p0 = _PAIRINGS.total()
    h0 = _HITS.value(cache="bls_verify")
    ok, batch = _flush_batch(items)      # replay: all memo hits
    assert ok
    assert _PAIRINGS.total() == p0, "replay must not re-verify"
    assert _HITS.value(cache="bls_verify") - h0 == len(items)
    assert batch.last_results is None or batch.last_results == []


def test_memoized_failure_raises_at_enqueue():
    bad = ([bls.SkToPk(9)], MSG_B, bls.Sign(9, MSG_A))
    ok, _ = _flush_batch([bad])
    assert not ok
    # the second enqueue finds the memoized False and fails immediately
    with bls.batched_verification():
        assert bls.Verify(bad[0][0], bad[1], bad[2]) is False


def test_duplicate_triples_share_one_lane():
    item = _sig_items(1)[0]
    with bls.batched_verification() as batch:
        bls.FastAggregateVerify(*item)
        bls.FastAggregateVerify(*item)
        assert len(batch.items) == 1
    assert batch.flush()


# ---------------------------------------------------------------------------
# Differential: RLC vs lanes vs python backend
# ---------------------------------------------------------------------------

def _item_matrix():
    good = _sig_items(3)
    return good + [
        ([bls.SkToPk(9)], MSG_B, bls.Sign(9, MSG_A)),   # wrong message
        ([INF_PK], MSG_A, bls.Sign(1, MSG_A)),          # invalid pubkey
    ]


def _per_item_results(items):
    ok, batch = _flush_batch(items)
    if batch.last_results is not None and len(batch.last_results) == len(items):
        return ok, batch.last_results
    return ok, [True] * len(items)    # rlc-pass: everything valid


def test_differential_rlc_vs_lanes_vs_oracle():
    items = _item_matrix()
    oracle = [bls.FastAggregateVerify(pks, m, s) if len(pks) != 1
              else bls.Verify(pks[0], m, s) for pks, m, s in items]
    bls.clear_verify_memo()
    ok_rlc, res_rlc = _per_item_results(items)
    bls.clear_verify_memo()
    with _rlc_env("0"):
        ok_lanes, res_lanes = _per_item_results(items)
    assert res_rlc == res_lanes == oracle
    assert ok_rlc == ok_lanes == all(oracle)
    if _native_available():
        bls.use_native()
        bls.clear_verify_memo()
        ok_n, res_n = _per_item_results(items)
        assert (ok_n, res_n) == (ok_rlc, res_rlc)


def _native_available():
    from consensus_specs_tpu.ops import native_bls
    return native_bls.available()


# ---------------------------------------------------------------------------
# Deferred raw pairing checks (the KZG batch fold)
# ---------------------------------------------------------------------------

def test_defer_pairing_check_requires_scope_and_rlc():
    pairs = [(G1_GENERATOR, G2_GENERATOR)]
    assert not bls.defer_pairing_check(pairs)          # no active scope
    with _rlc_env("0"):
        with bls.batched_verification():
            assert not bls.defer_pairing_check(pairs)  # rlc off
    with _rlc_env("1"):
        with bls.batched_verification() as batch:
            assert bls.defer_pairing_check(pairs, label="t")
            assert len(batch.pairing_checks) == 1
            batch.pairing_checks.clear()               # don't evaluate


def test_deferred_pairing_check_folds_and_bisects():
    # a trivially-true product: e(P, Q) * e(-P, Q) == 1
    good_pairs = [(G1_GENERATOR, G2_GENERATOR),
                  (-G1_GENERATOR, G2_GENERATOR)]
    bad_pairs = [(G1_GENERATOR, G2_GENERATOR)]         # e(G1, G2) != 1
    item = _sig_items(1)[0]
    with _rlc_env("1"):
        p0 = _PAIRINGS.total()
        with bls.batched_verification() as batch:
            bls.FastAggregateVerify(*item)
            assert bls.defer_pairing_check(good_pairs, label="ok")
        assert batch.flush()
        assert _PAIRINGS.total() - p0 == 1             # sig + check: 1 pairing
        bls.clear_verify_memo()
        with bls.batched_verification() as batch:
            bls.FastAggregateVerify(*item)
            assert bls.defer_pairing_check(good_pairs, label="ok")
            assert bls.defer_pairing_check(bad_pairs, label="bad")
        assert not batch.flush()
        assert batch.last_results == [True]
        assert batch.last_pairing_results == [True, False]


# ---------------------------------------------------------------------------
# Device (jax) path: the numpy-kernel mirror executes the identical
# kernel source eagerly, so this differential covers the device math
# without paying XLA compiles (same rationale as test_numpy_kernels.py;
# import-time switch, hence the subprocess)
# ---------------------------------------------------------------------------

_NUMPY_RLC_CHECK = r"""
import sys
sys.path.insert(0, %(repo)r)
from consensus_specs_tpu.ops.jax_bls.backend import NUMPY_KERNELS
assert NUMPY_KERNELS
from consensus_specs_tpu.ops.bls12_381.curve import g2_from_compressed
from consensus_specs_tpu.obs import registry
from consensus_specs_tpu.utils import bls

bls.use_py()
msg = b"rlc-np" * 6
pks = [bls.SkToPk(i) for i in (1, 2, 3)]
agg = bls.Aggregate([bls.Sign(i, msg) for i in (1, 2, 3)])
pk2, msg2 = bls.SkToPk(5), b"\x11" * 32
sig2 = bls.Sign(5, msg2)

bls.use_jax()
pairings = registry.counter("bls.pairings")
with bls.batched_verification() as batch:
    assert bls.FastAggregateVerify(pks, msg, agg)
    assert bls.Verify(pk2, msg2, sig2)
assert batch.flush()
assert pairings.total() == 1, pairings.total()

# forged pair whose sum verifies must be rejected + bisected
bls.clear_verify_memo()
s1, s2 = bls.Sign(11, msg), bls.Sign(12, msg2)
D = g2_from_compressed(bls.Sign(99, b"delta"))
f1 = (g2_from_compressed(s1) + D).to_compressed()
f2 = (g2_from_compressed(s2) - D).to_compressed()
with bls.batched_verification() as batch:
    bls.Verify(bls.SkToPk(11), msg, f1)
    bls.Verify(bls.SkToPk(12), msg2, f2)
assert not batch.flush()
assert batch.last_results == [False, False], batch.last_results
print("NUMPY-RLC-OK")
"""


@pytest.mark.skipif(
    not os.environ.get("CS_TPU_HEAVY") == "1",
    reason="numpy-mirror RLC differential subprocess (CS_TPU_HEAVY=1)")
def test_numpy_kernel_rlc_differential():
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, CS_TPU_NUMPY_KERNELS="1")
    proc = subprocess.run(
        [sys.executable, "-c", _NUMPY_RLC_CHECK % {"repo": repo}],
        env=env, capture_output=True, timeout=600, cwd=repo)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    assert b"NUMPY-RLC-OK" in proc.stdout


_NUMPY_PK_ROWS_CHECK = r"""
import sys
sys.path.insert(0, %(repo)r)
from consensus_specs_tpu.ops.jax_bls.backend import NUMPY_KERNELS
assert NUMPY_KERNELS
from consensus_specs_tpu.obs import registry
from consensus_specs_tpu.utils import bls

bls.use_py()
msg = b"rows" * 8
pks = [bls.SkToPk(i) for i in (1, 2, 3)]
agg = bls.Aggregate([bls.Sign(i, msg) for i in (1, 2, 3)])
singles = [(bls.SkToPk(i), bytes([i]) * 32, bls.Sign(i, bytes([i]) * 32))
           for i in (5, 6)]
bls.use_jax()
rows = registry.counter("bls.rlc.pk_rows")
with bls.batched_verification() as batch:
    assert bls.FastAggregateVerify(pks, msg, agg)
    for pk, m, sig in singles:
        assert bls.Verify(pk, m, sig)
assert batch.flush()
print("PK-ROWS", rows.value(kind="live"), rows.value(kind="dispatched"))
"""


def test_rlc_pk_rows_count_live_and_dispatched_rows():
    """One RLC flush of a 3-key aggregate and two singles on the numpy
    kernels (lane bucket = the 3 items): live rows are the 5 keys,
    dispatched rows the 3 lanes x 8 (the padded pubkey width's floor)."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, CS_TPU_NUMPY_KERNELS="1")
    proc = subprocess.run(
        [sys.executable, "-c", _NUMPY_PK_ROWS_CHECK % {"repo": repo}],
        env=env, capture_output=True, timeout=600, cwd=repo)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    assert b"PK-ROWS 5 24" in proc.stdout, proc.stdout.decode()[-500:]


_EARLY = registry.counter("bls.rlc.early_dispatch")


@pytest.mark.parametrize("kind", ["pubkey", "signature"])
@pytest.mark.parametrize("position", [0, 1, 2])
def test_check_jax_invalid_item_is_structural(kind, position, monkeypatch):
    """The device combiner answers None (bisect) for an invalid pubkey
    or signature wherever it sits.  An invalid pubkey stops it before
    any device work; an invalid signature after the pubkey side was
    launched, which it discards, and before the signature MSM and the
    pairing."""
    from consensus_specs_tpu.ops import bls_jax
    from consensus_specs_tpu.ops.jax_bls import pairing as PR

    def no_device(*_args, **_kw):
        raise AssertionError("an invalid batch reached the device")
    launched = []

    def pubkey_phase(*args):
        launched.append(args)
        return object()
    items = _sig_items(3)
    pks, msg, sig = items[position]
    if kind == "pubkey":
        items[position] = (pks[:-1] + [INF_PK], msg, sig)
        pubkey_phase = no_device
    else:
        items[position] = (pks, msg, b"\x00" * 96)
    monkeypatch.setattr(bls_jax, "rlc_pubkey_phase", pubkey_phase)
    for mod, name in ((bls_jax, "rlc_signature_phase"),
                      (bls_jax, "_j_g2_scale_sum"),
                      (PR, "staged_product_pairing_check")):
        monkeypatch.setattr(mod, name, no_device)
    used, dropped = (_EARLY.value(outcome="used"),
                     _EARLY.value(outcome="discarded"))
    assert bls_rlc._check_jax(items, (), [1, 2, 3]) is None
    assert len(launched) == (kind == "signature")
    assert _EARLY.value(outcome="used") == used
    assert _EARLY.value(outcome="discarded") - dropped == len(launched)


_NUMPY_ORDER_CHECK = r"""
import sys
sys.path.insert(0, %(repo)r)
from consensus_specs_tpu.ops.jax_bls.backend import NUMPY_KERNELS
assert NUMPY_KERNELS
from consensus_specs_tpu.obs import registry
from consensus_specs_tpu.ops import bls_jax, bls_rlc
from consensus_specs_tpu.utils import bls

bls.use_py()
msg = b"ord" * 8
pks = [bls.SkToPk(i) for i in (1, 2, 3)]
valid = [(pks, msg, bls.Aggregate([bls.Sign(i, msg) for i in (1, 2, 3)]))]
valid += [([bls.SkToPk(i)], bytes([i]) * 32, bls.Sign(i, bytes([i]) * 32))
          for i in (5, 6)]
# a well-formed signature over another message
wrong = valid[:2] + [(valid[2][0], valid[2][1], bls.Sign(6, msg))]

calls = []
tree_sum, decompress = bls_jax._j_tree_sum, bls_jax._decompress_g2
def traced_tree_sum(*a):
    calls.append("tree_sum")
    return tree_sum(*a)
def traced_decompress(*a):
    calls.append("decompress")
    return decompress(*a)
bls_jax._j_tree_sum = traced_tree_sum
bls_jax._decompress_g2 = traced_decompress
early = registry.counter("bls.rlc.early_dispatch")
for items in (valid, wrong):
    scalars = bls_rlc.derive_scalars(items)
    calls.clear()
    used = early.value(outcome="used")
    got = bls_rlc._check_jax(items, (), scalars)
    print("VERDICT", got, bls_rlc._check_py(items, (), scalars))
    print("FIRST", calls[0], calls.count("decompress"))
    print("USED", early.value(outcome="used") - used)
"""


def test_check_jax_launches_pubkey_side_before_decompression():
    """``_check_jax`` issues the pubkey tree sum before it decodes the
    first signature, counts ``bls.rlc.early_dispatch{outcome=used}``
    once a flush, and answers as ``_check_py`` on a valid batch and on
    one with a wrong but well-formed signature (numpy kernels)."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, CS_TPU_NUMPY_KERNELS="1")
    proc = subprocess.run(
        [sys.executable, "-c", _NUMPY_ORDER_CHECK % {"repo": repo}],
        env=env, capture_output=True, timeout=600, cwd=repo)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = proc.stdout.decode().splitlines()
    assert [ln for ln in lines if ln.startswith("VERDICT")] == [
        "VERDICT True True", "VERDICT False False"], lines
    assert [ln for ln in lines if ln.startswith("FIRST")] == [
        "FIRST tree_sum 3"] * 2, lines
    assert [ln for ln in lines if ln.startswith("USED")] == [
        "USED 1"] * 2, lines


# ---------------------------------------------------------------------------
# Full-block differential: every enqueue site (proposer signature,
# randao, attestations, altair sync aggregate) through one flush
# ---------------------------------------------------------------------------

def _build_signed_full_block(spec, state):
    from consensus_specs_tpu.test_infra.attestations import (
        get_valid_attestation)
    from consensus_specs_tpu.test_infra.block import (
        build_empty_block_for_next_slot, next_slots,
        state_transition_and_sign_block)
    attestation = get_valid_attestation(spec, state, signed=True)
    next_slots(spec, state, spec.MIN_ATTESTATION_INCLUSION_DELAY)
    block = build_empty_block_for_next_slot(spec, state)
    block.body.attestations.append(attestation)
    if hasattr(spec, "SyncAggregate"):
        from consensus_specs_tpu.test_infra.sync_committee import (
            compute_aggregate_sync_committee_signature,
            compute_committee_indices)
        committee_indices = compute_committee_indices(state)
        block.body.sync_aggregate = spec.SyncAggregate(
            sync_committee_bits=[True] * len(committee_indices),
            sync_committee_signature=(
                compute_aggregate_sync_committee_signature(
                    spec, state, block.slot - 1, committee_indices)))
    return state_transition_and_sign_block(spec, state, block)


def _full_block_differential(spec, state):
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    pre = state.copy()
    signed_block = _build_signed_full_block(spec, state)
    # replay the same signed block under both flush strategies
    bls.clear_verify_memo()
    s_rlc, s_lanes = pre.copy(), pre.copy()
    p0, r0 = _PAIRINGS.total(), _FLUSH.value(path="rlc")
    with _rlc_env("1"):
        spec.state_transition(s_rlc, signed_block, True)
    assert _FLUSH.value(path="rlc") - r0 == 1
    assert _PAIRINGS.total() - p0 == 1, \
        "a full block (proposer + randao + attestation [+ sync "\
        "aggregate]) must verify with ONE pairing"
    bls.clear_verify_memo()
    with _rlc_env("0"):
        spec.state_transition(s_lanes, signed_block, True)
    assert hash_tree_root(s_rlc) == hash_tree_root(s_lanes) \
        == hash_tree_root(state)


def test_full_block_differential_all_enqueue_sites():
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.test_infra.context import (
        _get_genesis_state, default_balances, default_activation_threshold)
    old_active = bls.bls_active
    bls.bls_active = True
    try:
        for fork in ("phase0", "altair"):
            spec = build_spec(fork, "minimal")
            state = _get_genesis_state(spec, default_balances,
                                       default_activation_threshold)
            _full_block_differential(spec, state)
    finally:
        bls.bls_active = old_active


@pytest.mark.slow
def test_blob_kzg_batch_defers_into_block_flush():
    from consensus_specs_tpu.ops import kzg as K
    setup = K.trusted_setup("minimal")
    width = setup.FIELD_ELEMENTS_PER_BLOB
    import random
    rng = random.Random(7)
    blob = b"".join(rng.randrange(K.BLS_MODULUS).to_bytes(32, "big")
                    for _ in range(width))
    commitment = K.blob_to_kzg_commitment(blob, setup)
    proof = K.compute_blob_kzg_proof(blob, commitment, setup)
    item = _sig_items(1)[0]
    with _rlc_env("1"):
        p0 = _PAIRINGS.total()
        with bls.batched_verification() as batch:
            bls.FastAggregateVerify(*item)
            assert K.verify_blob_kzg_proof_batch(
                [blob], [commitment], [proof], setup)
        assert batch.flush()
        assert _PAIRINGS.total() - p0 == 1, \
            "block signatures + blob-KZG batch must share ONE pairing"
        # wrong proof: the flush fails and the bisect blames the kzg check
        bls.clear_verify_memo()
        blob2 = b"".join(rng.randrange(K.BLS_MODULUS).to_bytes(32, "big")
                         for _ in range(width))
        bad_proof = K.compute_blob_kzg_proof(blob2, commitment, setup)
        with bls.batched_verification() as batch:
            bls.FastAggregateVerify(*item)
            assert K.verify_blob_kzg_proof_batch(
                [blob], [commitment], [bad_proof], setup)
        assert not batch.flush()
        assert batch.last_results == [True]
        assert batch.last_pairing_results == [False]
    # outside a scope the eager path still answers False directly
    assert not K.verify_blob_kzg_proof_batch(
        [blob], [commitment], [bad_proof], setup)
