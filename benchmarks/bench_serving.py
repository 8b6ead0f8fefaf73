"""Block-serving pipeline benchmark (``make bench-serving-smoke``,
CI-wired).

Replays the SAME captured load streams (``sim/load.py`` — equivocating
siblings, ex-ante reorg races) through two lanes of the serving surface:

* **sync control** — ``BlockServer(window=1)`` under ``CS_TPU_SERVING=0``:
  every event through the per-block spec path, per-block signature
  flushes, whole-state ``copy()`` snapshots;
* **pipelined** — ``CS_TPU_SERVING=1``, configured window: window
  batching + cross-block attestation prep, the window's ONE combined
  RLC flush overlapped on the worker lane, chunk-level state clones.

Counter-asserted contracts (nonzero exit on any violation):

1. **byte-identity** — both lanes reduce to the same deep store digest
   (every block's post-state root, every latest message) and report the
   same per-block accept/reject map;
2. **one pairing per window** — the pipelined lane's ``bls.pairings``
   delta equals its ``serving.windows`` delta (the sibling-dedup fold),
   strictly below the sync lane's per-block pairing count;
3. **full pipelined service** — ``serving.blocks{path=pipelined}``
   covers every block, zero ``serving.fallbacks`` either lane;
4. **epoch-commit census under overlap** — the ``state_arrays.commits``
   delta is lane-identical (the flush overlap never double-commits or
   skips a balance-family flush);
5. **throughput** — sustained slots/sec (best-of-reps, aggregated over
   the stream mix) is strictly higher pipelined than sync;
6. **chunk-level snapshot cost** — on a large registry (mainnet preset,
   1M validators in the full shape), ``clone_state``
   beats ``state.copy()`` while staying root-identical, including after
   divergent mutation of both snapshots.

p50/p99 block-ingest latency comes from the ``serving.ingest_latency``
histogram; the pipelined lane trades per-block latency (blocks wait for
their window barrier) for throughput, so latency is reported, not
bounded.  ``--smoke`` is the CI shape; the full shape
(``--clone-validators 1048576`` with ``make warm`` caches) is BASELINE.md
config #5's registry.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_state_arrays import build_state  # noqa: E402


def _run_lane(spec, stream, serving, window, reps):
    """Best-of-reps replay of one stream through one lane.  Returns the
    lane record: wall time, counter deltas, latency quantiles, digest,
    per-block results (digest/results asserted rep-stable)."""
    from consensus_specs_tpu.obs import registry as obs_registry
    from consensus_specs_tpu.serving import BlockServer
    from consensus_specs_tpu.sim import load
    from consensus_specs_tpu.test_infra.metrics import counting
    from consensus_specs_tpu.utils import bls

    os.environ["CS_TPU_SERVING"] = "1" if serving else "0"
    best = None
    for _ in range(reps):
        bls.clear_verify_memo()         # real pairings every rep
        obs_registry.reset("serving.")
        store = load.anchor_store(spec, stream)
        server = BlockServer(spec, store, window=window)
        t0 = time.perf_counter()
        with counting() as delta:
            results = load.serve(server, stream)
        wall = time.perf_counter() - t0
        hist = obs_registry.metrics()["serving.ingest_latency"].value()
        digest = load.store_digest(spec, store)
        if best is not None:
            assert digest == best["digest"], \
                f"{stream.name}: digest drifted across reps"
            assert results == best["results"], \
                f"{stream.name}: per-block results drifted across reps"
        if best is None or wall < best["wall_s"]:
            best = {"wall_s": wall, "delta": delta, "digest": digest,
                    "results": results,
                    "p50": hist["p50"], "p99": hist["p99"]}
    return best


def _clone_phase(preset, n, reps):
    """Chunk-level snapshot vs whole-state copy on a large registry:
    cost ratio plus a divergent-mutation root differential."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.serving import clone_state
    from consensus_specs_tpu.utils.ssz import hash_tree_root

    spec = build_spec("altair", preset)
    t0 = time.perf_counter()
    state = build_state(spec, n)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    base_root = bytes(hash_tree_root(state))    # warm tree + root memo
    merkle_s = time.perf_counter() - t0

    t_copy = min(_timed(state.copy) for _ in range(reps))
    t_clone = min(_timed(lambda: clone_state(state)) for _ in range(reps))

    # byte-identity, including off the memoized-root happy path: mutate
    # both snapshots the same way (a fast field and a lazy field) and
    # demand they re-merkleize to the same NEW root, source untouched
    ref, cl = state.copy(), clone_state(state)
    for st in (ref, cl):
        st.balances[1] = st.balances[1] + 7
        st.validators[0].effective_balance = \
            st.validators[0].effective_balance + 1
    ref_root = bytes(hash_tree_root(ref))
    assert bytes(hash_tree_root(cl)) == ref_root, \
        "mutated chunk-level clone diverged from mutated full copy"
    assert ref_root != base_root
    assert bytes(hash_tree_root(state)) == base_root, \
        "cloning/mutating snapshots disturbed the source state"
    return {
        "preset": preset, "validators": n,
        "build_s": round(build_s, 3), "merkle_s": round(merkle_s, 3),
        "copy_s": round(t_copy, 5), "clone_s": round(t_clone, 5),
        "clone_speedup": round(t_copy / t_clone, 1) if t_clone else None,
    }


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--reps", type=int, default=3,
                    help="replays per lane per stream (best-of)")
    ap.add_argument("--window", type=int, default=8,
                    help="pipelined window depth (deeper than the engine "
                         "default: more blocks per fold widens the "
                         "throughput margin; 0 = CS_TPU_SERVING_WINDOW)")
    ap.add_argument("--clone-preset", default="mainnet")
    ap.add_argument("--clone-validators", type=int, default=1 << 20,
                    help="registry size for the snapshot-cost phase")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI shape + counter asserts")
    args = ap.parse_args()
    if args.smoke:
        args.clone_validators = 1 << 16
        args.reps = 3

    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.obs import export
    from consensus_specs_tpu.sim import load
    from consensus_specs_tpu.utils import bls

    # real signatures through the fastest backend: the pairing census
    # (windows == pairings) is the point of the pipeline
    bls.use_fastest()
    bls.bls_active = True
    spec = build_spec("phase0", "minimal")

    streams = [load.generate(spec, seed=args.seed, name=name)
               for name in load.DEFAULT_MIX]
    serving_prev = os.environ.get("CS_TPU_SERVING")

    lanes, total = {}, {}
    try:
        for lane, serving, window in (("sync", False, 1),
                                      ("pipelined", True, args.window)):
            per_stream = []
            for stream in streams:
                rec = _run_lane(spec, stream, serving, window, args.reps)
                rec["stream"] = stream.describe()
                per_stream.append(rec)
            wall = sum(r["wall_s"] for r in per_stream)
            slots = sum(s.result.slots for s in streams)
            lanes[lane] = per_stream
            total[lane] = {
                "wall_s": round(wall, 3),
                "slots_per_s": round(slots / wall, 1) if wall else None,
                "p50_ms": round(max(r["p50"] for r in per_stream) * 1e3, 3),
                "p99_ms": round(max(r["p99"] for r in per_stream) * 1e3, 3),
            }
    finally:
        if serving_prev is None:
            os.environ.pop("CS_TPU_SERVING", None)
        else:
            os.environ["CS_TPU_SERVING"] = serving_prev

    clone = _clone_phase(args.clone_preset, args.clone_validators, args.reps)

    snap = export.snapshot()
    export.assert_schema(snap, require_nonempty=("serving.",))
    result = {
        "metric": "block-serving pipeline",
        "seed": args.seed, "reps": args.reps,
        "streams": [s.describe() for s in streams],
        "blocks": sum(s.n_blocks for s in streams),
        "slots": sum(s.result.slots for s in streams),
        "bls_backend": bls.backend_name(),
        "lanes": {
            lane: [{k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in r.items()
                    if k in ("stream", "wall_s", "p50", "p99")}
                   for r in recs]
            for lane, recs in lanes.items()},
        "total": total,
        "speedup": round(total["sync"]["wall_s"]
                         / total["pipelined"]["wall_s"], 2),
        "clone": clone,
    }
    print(json.dumps(result), flush=True)

    # the census guarantees (the smoke's reason to exist)
    for i, stream in enumerate(streams):
        sync, pipe = lanes["sync"][i], lanes["pipelined"][i]
        assert sync["digest"] == pipe["digest"], \
            f"{stream.name}: lane stores diverged"
        assert sync["results"] == pipe["results"], \
            f"{stream.name}: per-block verdicts diverged"
        ds, dp = sync["delta"], pipe["delta"]
        assert ds["serving.blocks{path=sync}"] == stream.n_blocks, \
            f"{stream.name}: sync lane missed blocks: {ds.nonzero()}"
        assert ds["serving.windows"] == 0
        assert dp["serving.blocks{path=pipelined}"] == stream.n_blocks, \
            f"{stream.name}: pipelined lane fell back: {dp.nonzero()}"
        assert dp["serving.blocks{path=sync}"] == 0
        for delta, lane in ((ds, "sync"), (dp, "pipelined")):
            fb = sum(v for k, v in delta.items()
                     if k.startswith("serving.fallbacks"))
            assert fb == 0, \
                f"{stream.name}/{lane}: unexpected fallbacks: " \
                f"{delta.nonzero()}"
        # one pairing per window (sibling/cross-block dedup): the sync
        # lane pays one flush pairing per accepted block
        windows = dp["serving.windows"]
        assert windows > 0
        assert dp["bls.pairings"] == windows, \
            f"{stream.name}: pairing census broke: " \
            f"{dp['bls.pairings']} pairings != {windows} windows"
        assert ds["bls.pairings"] > dp["bls.pairings"], \
            f"{stream.name}: window fold saved no pairings " \
            f"({ds['bls.pairings']} vs {dp['bls.pairings']})"
        assert ds["state_arrays.commits"] == dp["state_arrays.commits"], \
            f"{stream.name}: epoch-commit census diverged under overlap"
    assert total["pipelined"]["slots_per_s"] > total["sync"]["slots_per_s"], \
        f"pipelined lane not faster: {total}"
    assert clone["clone_speedup"] and clone["clone_speedup"] > 1.0, \
        f"chunk-level clone slower than state.copy(): {clone}"


if __name__ == "__main__":
    main()
