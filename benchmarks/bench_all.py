"""Extended benchmark matrix (BASELINE.md configs #1-#5).

``bench.py`` at the repo root stays the driver's single-line entry
(config #1).  This harness measures the full matrix and prints one JSON
line per config.  Python baselines are warmed and repeated.

Usage: python benchmarks/bench_all.py [--configs 1,2,3,4,5] [--validators N]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from consensus_specs_tpu.utils.jax_env import setup_compile_cache  # noqa: E402
setup_compile_cache()


def _timeit(fn, reps=3, warmup=1):
    from consensus_specs_tpu.utils import bls
    for _ in range(warmup):
        fn()
    t0 = time.time()
    for _ in range(reps):
        # time pairings, not dict hits: identical signatures across reps
        # would otherwise be served by the verification memo
        bls.clear_verify_memo()
        fn()
    return (time.time() - t0) / reps


def _want_jax() -> bool:
    """Measure the JAX path only on an accelerator (or when forced): on a
    bare CPU it pays minutes of XLA compile for sub-oracle throughput,
    and the native C backend is the production CPU path."""
    if os.environ.get("CS_TPU_BENCH_JAX") == "1":
        return True
    import jax
    return jax.default_backend() != "cpu"


def bench_fast_aggregate_verify(batch=16, n_keys=64):
    """Config #1: batched FastAggregateVerify vs warmed py oracle.
    Measures the native C backend (the CPU production path) and, when an
    accelerator is live, the batched JAX pipeline; reports the faster."""
    from consensus_specs_tpu.utils import bls

    bls.use_py()
    msg = b"bench-attestation-root"
    sks = list(range(1, 1 + n_keys))
    pks = [bls.SkToPk(sk) for sk in sks]
    agg = bls.Aggregate([bls.Sign(sk, msg) for sk in sks])

    py_per_verify = _timeit(
        lambda: bls.FastAggregateVerify(pks, msg, agg), reps=3, warmup=1)

    results = {}
    from consensus_specs_tpu.ops import native_bls
    if native_bls.available():
        bls.use_native()
        dt = _timeit(lambda: bls.FastAggregateVerify(pks, msg, agg), reps=3)
        results["native"] = 1.0 / dt
        bls.use_py()
    if _want_jax():
        from consensus_specs_tpu.ops import bls_jax
        items = [(pks, msg, agg)] * batch
        assert all(bls_jax.verify_aggregates_batch(items))
        dt = _timeit(lambda: bls_jax.verify_aggregates_batch(items), reps=3)
        results["jax"] = batch / dt
    if not results:
        results["py"] = 1.0 / py_per_verify
    best = max(results, key=results.get)
    per_sec = results[best]
    out = {"metric": f"FastAggregateVerify ({n_keys} pubkeys, batch {batch})",
           "value": round(per_sec, 3), "unit": "aggverify/s",
           "vs_baseline": round(per_sec * py_per_verify, 2),
           "backend": best}
    for name, v in results.items():
        out[f"{name}_per_sec"] = round(v, 3)
    return out


def _build_block_with_attestations(spec, state, max_atts):
    from consensus_specs_tpu.test_infra.attestations import (
        get_valid_attestation)
    from consensus_specs_tpu.test_infra.block import build_empty_block
    from consensus_specs_tpu.test_infra import block as blk

    target_slot = state.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY
    attestations = []
    # attestations for every committee of the eligible slot, duplicated up
    # to the cap (duplicates are valid blocks-wise and keep the crypto load
    # at MAX_ATTESTATIONS without an epoch-long build-up)
    committees = spec.get_committee_count_per_slot(
        state, spec.get_current_epoch(state))
    base = [get_valid_attestation(spec, state, state.slot, index=i,
                                  signed=True)
            for i in range(committees)]
    while len(attestations) < max_atts:
        attestations.extend(base[:max_atts - len(attestations)])
    block = build_empty_block(spec, state, target_slot)
    for att in attestations:
        block.body.attestations.append(att)
    return blk.state_transition_and_sign_block(spec, state.copy(), block), \
        block


def bench_process_block(n_validators=2048, max_atts=None):
    """Config #2: process_block wall-clock with a full attestation load,
    jax backend vs warmed py backend."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.test_infra.genesis import create_genesis_state
    from consensus_specs_tpu.utils import bls

    spec = build_spec("phase0", "mainnet")
    state = create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * n_validators,
        spec.MAX_EFFECTIVE_BALANCE)
    if max_atts is None:
        max_atts = spec.MAX_ATTESTATIONS
    # fixture signing: any backend produces identical (deterministic)
    # signatures; the native library builds the 128-attestation block in
    # seconds where the oracle needs minutes
    from consensus_specs_tpu.ops import native_bls
    bls.use_native() if native_bls.available() else bls.use_py()
    signed_block, _ = _build_block_with_attestations(spec, state, max_atts)
    bls.use_py()

    def run(backend):
        backend()
        work_state = state.copy()
        spec.process_slots(work_state, signed_block.message.slot)
        t0 = time.time()
        spec.process_block(work_state, signed_block.message)
        return time.time() - t0

    py_dt = run(bls.use_py)
    results = {}
    if native_bls.available():
        run(bls.use_native)  # warm decode caches
        results["native"] = min(run(bls.use_native), run(bls.use_native))
    if _want_jax():
        run(bls.use_jax)  # compile
        results["jax"] = min(run(bls.use_jax), run(bls.use_jax))
    if not results:
        results["py"] = py_dt
    best = min(results, key=results.get)
    dt = results[best]
    out = {"metric": f"process_block ({max_atts} attestations, "
                     f"{n_validators} validators)",
           "value": round(dt, 3), "unit": "s/block",
           "vs_baseline": round(py_dt / dt, 2), "backend": best}
    for name, v in results.items():
        out[f"{name}_s"] = round(v, 3)
    return out


def bench_sync_aggregate():
    """Config #3: altair process_sync_aggregate (512 pubkeys, mainnet)."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.test_infra.genesis import create_genesis_state
    from consensus_specs_tpu.test_infra.sync_committee import (
        compute_aggregate_sync_committee_signature, compute_committee_indices)
    from consensus_specs_tpu.test_infra.block import next_slot
    from consensus_specs_tpu.utils import bls

    spec = build_spec("altair", "mainnet")
    bls.use_py()
    state = create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * 1024,
        spec.MAX_EFFECTIVE_BALANCE)
    next_slot(spec, state)
    committee_indices = compute_committee_indices(state)
    signature = compute_aggregate_sync_committee_signature(
        spec, state, state.slot - 1, committee_indices)
    aggregate = spec.SyncAggregate(
        sync_committee_bits=[True] * spec.SYNC_COMMITTEE_SIZE,
        sync_committee_signature=signature)

    def run():
        spec.process_sync_aggregate(state.copy(), aggregate)

    bls.use_py()
    py_dt = _timeit(run, reps=2, warmup=1)
    results = {}
    from consensus_specs_tpu.ops import native_bls
    if native_bls.available():
        bls.use_native()
        results["native"] = _timeit(run, reps=3, warmup=1)
    if _want_jax():
        bls.use_jax()
        results["jax"] = _timeit(run, reps=3, warmup=1)
    if not results:
        results["py"] = py_dt
    bls.use_py()
    best = min(results, key=results.get)
    dt = results[best]
    out = {"metric": "process_sync_aggregate (512 pubkeys, mainnet)",
           "value": round(dt, 3), "unit": "s/op",
           "vs_baseline": round(py_dt / dt, 2), "backend": best}
    for name, v in results.items():
        out[f"{name}_s"] = round(v, 3)
    return out


def bench_epoch_replay(n_validators=4096, slots=8):
    """Config #5 (scaled): slots of state_transition incl. epoch boundary.
    Hash/merkleization bound; BLS disabled like the reference's fastest
    path comparison."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.test_infra.genesis import create_genesis_state
    from consensus_specs_tpu.test_infra.block import (
        build_empty_block_for_next_slot, state_transition_and_sign_block)
    from consensus_specs_tpu.utils import bls

    spec = build_spec("phase0", "minimal")
    bls.bls_active = False
    state = create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * n_validators,
        spec.MAX_EFFECTIVE_BALANCE)
    t0 = time.time()
    for _ in range(slots):
        block = build_empty_block_for_next_slot(spec, state)
        state_transition_and_sign_block(spec, state, block)
    dt = time.time() - t0
    bls.bls_active = True
    return {"metric": f"epoch replay ({slots} slots, {n_validators} "
                      "validators, bls off)",
            "value": round(dt, 3), "unit": "s/epoch", "vs_baseline": 1.0}


# validator counts for the config #5 loop-vs-vectorized engine
# comparison (overridden by --epoch-shapes)
_EPOCH_SHAPES = [16384]


def _synthetic_registry_state(spec, n_validators, seed=5):
    """A mainnet-shaped altair state at epoch 3 with ``n_validators``
    active validators: fabricated pubkeys (the epoch path never reads
    them), ~2% slashed, a few low-balance validators, ~75% full
    participation.  Built directly (no deposits, no real keys) so the
    1M-validator shape is constructible in seconds, not hours."""
    import random as _random
    rng = _random.Random(seed)
    max_eb = int(spec.MAX_EFFECTIVE_BALANCE)
    increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    far = int(spec.FAR_FUTURE_EPOCH)
    epoch = 3
    Validator = spec.Validator
    filler = b"\xaa" * 42
    validators, balances, participation, scores = [], [], [], []
    for i in range(n_validators):
        slashed = rng.random() < 0.02
        eff = max_eb if rng.random() > 0.05 else max_eb - increment
        validators.append(Validator(
            pubkey=i.to_bytes(6, "little") + filler,
            effective_balance=eff,
            slashed=slashed,
            exit_epoch=far,
            withdrawable_epoch=epoch + rng.randrange(1, 16) if slashed
            else far,
        ))
        balances.append(eff + rng.randrange(0, increment))
        participation.append(7 if rng.random() < 0.75 else rng.randrange(8))
        scores.append(0 if rng.random() < 0.9 else rng.randrange(1, 20))
    state = spec.BeaconState(
        slot=epoch * int(spec.SLOTS_PER_EPOCH),
        validators=validators, balances=balances,
        previous_epoch_participation=participation,
        current_epoch_participation=participation,
        inactivity_scores=scores,
    )
    state.finalized_checkpoint.epoch = 1    # recent finality: no leak
    # warm the registry subtree memo: production merkleizes the state
    # every slot (process_slot state-root caching), so by any epoch
    # boundary the validators root is already cached — a freshly built
    # synthetic registry must not charge that first-ever merkleization
    # to either engine
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    hash_tree_root(state.validators)
    return state


def _bench_epoch_engine_at(n_validators):
    """One shape of the loop-vs-vectorized comparison: altair
    ``process_rewards_and_penalties`` (the participation-flag path that
    carries bellatrix..eip7594 by inheritance) through the per-validator
    spec loop vs the columnar engine.  ``vec_cold_s`` includes the
    once-per-epoch snapshot extraction; ``vec_warm_s`` is the
    steady-state cost with the snapshot amortized across the five epoch
    stages (and unchanged registries)."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.ops import epoch_kernels as ek
    from consensus_specs_tpu.utils.ssz import hash_tree_root

    spec = build_spec("altair", "mainnet")
    state = _synthetic_registry_state(spec, n_validators)
    s_loop = state.copy()

    ek.use_loops()
    t0 = time.time()
    spec.process_rewards_and_penalties(s_loop)
    loop_s = time.time() - t0

    ek.use_vectorized()
    t0 = time.time()
    spec.process_rewards_and_penalties(state)
    vec_cold_s = time.time() - t0
    # differential check rides every bench run: same pre-state, both
    # engines, identical post-balances root
    assert hash_tree_root(state.balances) == hash_tree_root(s_loop.balances)

    warm = []
    for _ in range(3):
        t0 = time.time()
        spec.process_rewards_and_penalties(state)
        warm.append(time.time() - t0)
    vec_warm_s = min(warm)
    ek.use_auto()
    return {"validators": n_validators, "loop_s": round(loop_s, 3),
            "vec_cold_s": round(vec_cold_s, 3),
            "vec_warm_s": round(vec_warm_s, 4),
            "speedup_cold": round(loop_s / vec_cold_s, 1),
            "speedup_warm": round(loop_s / vec_warm_s, 1)}


def bench_epoch_transition():
    """Config #5: the BASELINE epoch-replay metric (now running through
    the vectorized engine by default) plus the explicit loop-vs-
    vectorized ``process_rewards_and_penalties`` comparison at the
    --epoch-shapes registry sizes."""
    out = bench_epoch_replay()
    out["engine"] = [_bench_epoch_engine_at(n) for n in _EPOCH_SHAPES]
    return out


def bench_blob_batch(n_blobs=6):
    """Config #4: deneb ``verify_blob_kzg_proof_batch`` over 6 blobs
    (mainnet setup) vs serial per-blob verification.  The batch path is
    the spec's random-linear-combination optimization - two MSMs and ONE
    pairing check for the whole batch vs one pairing per blob
    (``specs/deneb/polynomial-commitments.md`` verify_blob_kzg_proof_batch)."""
    import random as _random
    from consensus_specs_tpu.ops import kzg as K

    setup = K.trusted_setup("mainnet")
    width = setup.FIELD_ELEMENTS_PER_BLOB
    rng = _random.Random(4)
    blobs = [b"".join((rng.randrange(K.BLS_MODULUS)).to_bytes(32, "big")
                      for _ in range(width)) for _ in range(n_blobs)]
    commitments = [K.blob_to_kzg_commitment(b, setup) for b in blobs]
    proofs = [K.compute_blob_kzg_proof(b, c, setup)
              for b, c in zip(blobs, commitments)]

    def serial():
        assert all(K.verify_blob_kzg_proof(b, c, p, setup)
                   for b, c, p in zip(blobs, commitments, proofs))

    def batched():
        assert K.verify_blob_kzg_proof_batch(
            blobs, commitments, proofs, setup)

    serial_dt = _timeit(serial, reps=2, warmup=1)
    batch_dt = _timeit(batched, reps=2, warmup=1)
    return {"metric": f"verify_blob_kzg_proof_batch ({n_blobs} blobs, "
                      "mainnet)",
            "value": round(batch_dt, 3), "unit": "s/batch",
            "vs_baseline": round(serial_dt / batch_dt, 2)}


CONFIGS = {
    "1": bench_fast_aggregate_verify,
    "2": bench_process_block,
    "3": bench_sync_aggregate,
    "4": bench_blob_batch,
    "5": bench_epoch_transition,
}


def main():
    global _EPOCH_SHAPES
    parser = argparse.ArgumentParser()
    parser.add_argument("--configs", default="1,2,3,4,5")
    parser.add_argument("--epoch-shapes", default="16384",
                        help="comma-separated validator counts for the "
                             "config #5 loop-vs-vectorized epoch-engine "
                             "comparison (e.g. 16384,262144,1048576)")
    parser.add_argument("--profile", action="store_true",
                        help="print the per-stage span breakdown "
                             "(obs.tracing) after each config")
    ns = parser.parse_args()
    _EPOCH_SHAPES = [int(s) for s in ns.epoch_shapes.split(",")]
    if ns.profile:
        from consensus_specs_tpu.obs import tracing
        tracing.enable()
    for key in ns.configs.split(","):
        if ns.profile:
            tracing.reset()
        result = CONFIGS[key.strip()]()
        print(json.dumps(result), flush=True)
        if ns.profile:
            print(json.dumps({"config": key.strip(),
                              "stages": tracing.stats()}), flush=True)


if __name__ == "__main__":
    main()
