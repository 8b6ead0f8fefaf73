"""StateArrays store benchmark (``make bench-state-smoke``, CI-wired).

Drives an N-validator altair state through an S-slot replay with the
vectorized engines on, then forks R concurrent replays off one base
snapshot — census-asserting the copy-on-write column store's contracts
via the ``state_arrays.*`` telemetry counters:

1. **extraction census** — the registry is extracted at most once per
   epoch transition (exactly once TOTAL in an empty-slot replay: the
   lineage-attached columns stay structurally valid across epochs);
2. **one commit per epoch transition** — the balance-family columns
   flush to SSZ chunks exactly once per ``process_epoch``, not once per
   sub-transition;
3. **cheap snapshot/fork** — R replays forked from one base produce
   byte-identical state roots vs independent full-copy replays run
   with the store DISABLED (a true differential oracle) while sharing
   the base columns: zero registry re-extractions in the forks and a
   copy-on-write census strictly below columns x replays.

Exits nonzero on any violation.  ``--smoke`` runs the small CI shape;
the full shape (``--validators 1048576 --slots 32``) is BASELINE.md
config #5.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_state(spec, n):
    state = spec.BeaconState()
    v = spec.Validator(
        effective_balance=spec.MAX_EFFECTIVE_BALANCE,
        activation_epoch=0,
        exit_epoch=spec.FAR_FUTURE_EPOCH,
        withdrawable_epoch=spec.FAR_FUTURE_EPOCH)
    state.validators = [v] * n
    state.balances = [spec.MAX_EFFECTIVE_BALANCE] * n
    state.inactivity_scores = [0] * n
    state.previous_epoch_participation = [0] * n
    state.current_epoch_participation = [0] * n
    return state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--validators", type=int, default=262144)
    ap.add_argument("--slots", type=int, default=16,
                    help="replay window (minimal preset: 8 slots/epoch)")
    ap.add_argument("--replays", type=int, default=16,
                    help="concurrent replays forked from one snapshot")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI shape + counter asserts")
    args = ap.parse_args()
    if args.smoke:
        args.validators, args.slots, args.replays = 2048, 16, 16

    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.obs import export
    from consensus_specs_tpu.obs import registry as obs_registry
    from consensus_specs_tpu.state import arrays
    from consensus_specs_tpu.test_infra.metrics import counting
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.ssz import hash_tree_root

    bls.bls_active = False
    spec = build_spec("altair", "minimal")
    slots_per_epoch = int(spec.SLOTS_PER_EPOCH)
    assert arrays.enabled(), \
        "state-arrays store disabled (CS_TPU_STATE_ARRAYS=0?)"

    t0 = time.time()
    state = build_state(spec, args.validators)
    build_s = time.time() - t0

    # warm-up: the genesis-epoch transition no-ops; measure from epoch 1
    spec.process_slots(state, slots_per_epoch)
    obs_registry.reset("state_arrays.")
    obs_registry.reset("epoch.")
    obs_registry.reset("cache.")

    # -- 1+2: S-slot replay, extraction + commit census -------------------
    epochs = args.slots // slots_per_epoch
    t0 = time.time()
    with counting() as replay_delta:
        spec.process_slots(state, int(state.slot) + args.slots)
    replay_s = time.time() - t0
    extracts = replay_delta["state_arrays.extracts{column=registry}"] \
        + replay_delta["state_arrays.adoptions"]
    commits = replay_delta["state_arrays.commits"]

    # -- 3: R concurrent replays off one snapshot --------------------------
    base_root = bytes(hash_tree_root(state))
    arrays.registry_of(state)                  # base columns warm
    arrays.of(state).balances()
    half = int(spec.MAX_EFFECTIVE_BALANCE) // 2
    t0 = time.time()
    forks = [arrays.fork_state(state) for _ in range(args.replays)]
    fork_s = time.time() - t0
    t0 = time.time()
    with counting() as fork_delta:
        forked_roots = []
        for k, st in enumerate(forks):
            # distinct per-replay perturbation; halving a balance forces
            # the effective-balance hysteresis (registry COW) path
            st.balances[k % args.validators] = half + k
            spec.process_slots(st, int(st.slot) + slots_per_epoch)
            forked_roots.append(bytes(hash_tree_root(st)))
    forked_s = time.time() - t0
    cow_copies = fork_delta["state_arrays.cow_copies"]
    fork_extracts = fork_delta["state_arrays.extracts{column=registry}"]

    # independent leg runs with the store OFF (detached single-use
    # stores, no COW, no attach): a genuine differential oracle — a
    # store bug that corrupts a shared column cannot cancel out of the
    # forked-vs-independent root comparison
    arrays.use_fallback()
    t0 = time.time()
    independent_roots = []
    for k in range(args.replays):
        st = state.copy()
        st.balances[k % args.validators] = half + k
        spec.process_slots(st, int(st.slot) + slots_per_epoch)
        independent_roots.append(bytes(hash_tree_root(st)))
    independent_s = time.time() - t0
    arrays.use_auto()

    n_columns = len(arrays._COLUMNS)
    snap = export.snapshot()
    export.assert_schema(snap, require_nonempty=("state_arrays.",))
    result = {
        "metric": "state-arrays store",
        "validators": args.validators, "slots": args.slots,
        "epochs": epochs, "replays": args.replays,
        "build_s": round(build_s, 3),
        "replay_s": round(replay_s, 3),
        "slots_per_s": round(args.slots / replay_s, 1) if replay_s else None,
        "registry_extractions": extracts,
        "commits": commits,
        "fork_total_s": round(fork_s, 5),
        "fork_each_us": round(fork_s / args.replays * 1e6, 1),
        "cow_copies": cow_copies,
        "cow_bound": n_columns * args.replays,
        "forked_replays_s": round(forked_s, 3),
        "independent_replays_s": round(independent_s, 3),
        "obs": {k: v for k, v in snap["metrics"].items()
                if k.startswith(("state_arrays.", "epoch."))},
    }
    print(json.dumps(result), flush=True)

    # the census guarantees (the smoke's reason to exist)
    assert replay_delta["epoch.transition{path=vectorized}"] > 0, \
        "vectorized engine never committed during the replay"
    assert replay_delta["epoch.fallbacks{reason=guard}"] == 0, \
        "unexpected guard fallback"
    assert extracts <= epochs, \
        f"registry re-extracted within an epoch: {extracts} > {epochs}"
    assert commits == epochs, \
        f"expected one balance-family commit per epoch: {commits} != {epochs}"
    assert forked_roots == independent_roots, \
        "forked replays diverged from independent replays"
    assert bytes(hash_tree_root(state)) == base_root, \
        "a forked replay mutated the base snapshot"
    assert fork_extracts == 0, \
        f"forked replays re-extracted shared registry columns: {fork_extracts}"
    assert 0 < cow_copies < n_columns * args.replays, \
        f"copy-on-write census out of bounds: {cow_copies} vs " \
        f"{n_columns * args.replays}"


if __name__ == "__main__":
    main()
