"""Corpus fidelity replayer.

Loads emitted vectors back off disk and re-executes them through the
spec, proving the corpus the accelerated factory wrote is the corpus a
conforming client would accept: every decoded ``pre`` + input must
reproduce ``post`` (state roots compared), and every case whose
``post`` is absent must be REJECTED by the spec.  Run it twice —
engines on, then every ``CS_TPU_*=0`` — and a clean pass both times is
the end-to-end proof that no engine (RLC folds, vectorized epoch,
state arrays) leaked an optimistic result into a vector
(``make corpus-check``).

Covered formats (tests/formats/*): ``operations`` (part-name-dispatched
sub-transitions, including the stubbed-execution-engine
``execution_payload`` handler), ``epoch_processing`` (driven by the
``sub_transition`` meta key), ``sanity`` (``slots`` and ``blocks``),
and ``finality`` (sanity/blocks format).  Cases the four formats
cannot re-execute (hand-shaped epoch cases without the meta key,
block-level cases filed under an operations handler) are counted as
skips and listed with ``-v`` — a skip is visible, never silent.
"""
import argparse
import os
import sys

import yaml

from consensus_specs_tpu.utils import snappy

# operation part filename -> (spec type name, process function).  The
# repo's handlers don't map 1:1 onto operations (the combined
# ``slashing`` handler emits three different part kinds), so dispatch
# is by part name, which IS 1:1 (tests/formats/operations/README.md).
OPERATION_PARTS = {
    "attestation": ("Attestation", "process_attestation"),
    "attester_slashing": ("AttesterSlashing", "process_attester_slashing"),
    "proposer_slashing": ("ProposerSlashing", "process_proposer_slashing"),
    "deposit": ("Deposit", "process_deposit"),
    "voluntary_exit": ("SignedVoluntaryExit", "process_voluntary_exit"),
    "sync_aggregate": ("SyncAggregate", "process_sync_aggregate"),
    "address_change": ("SignedBLSToExecutionChange",
                       "process_bls_to_execution_change"),
    "execution_payload": ("ExecutionPayload", "process_withdrawals"),
    "block": ("BeaconBlock", "process_block_header"),
    "body": ("BeaconBlockBody", "process_execution_payload"),
}

REPLAYABLE_RUNNERS = ("operations", "epoch_processing", "sanity", "finality")

_REJECTIONS = (AssertionError, IndexError, KeyError, ValueError,
               ArithmeticError)


class Mismatch(Exception):
    """A vector the spec does not reproduce — corpus corruption or an
    engine fidelity bug; either way the replay run must fail."""


def _read_ssz(case_dir: str, name: str, typ):
    path = os.path.join(case_dir, f"{name}.ssz_snappy")
    with open(path, "rb") as f:
        from consensus_specs_tpu.utils.ssz import deserialize
        return deserialize(typ, snappy.decompress(f.read()))


def _read_meta(case_dir: str) -> dict:
    path = os.path.join(case_dir, "meta.yaml")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return yaml.safe_load(f) or {}


def _assert_post(spec, state, case_dir: str, label: str) -> None:
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    post = _read_ssz(case_dir, "post", spec.BeaconState)
    if hash_tree_root(state) != hash_tree_root(post):
        raise Mismatch(f"{label}: replayed state root differs from post")


def _expect_rejection(fn, label: str) -> None:
    try:
        fn()
    except _REJECTIONS:
        return
    raise Mismatch(f"{label}: expected-invalid input was accepted")


def _replay_operations(spec, case_dir: str, parts, meta) -> str:
    op_names = [p for p in parts if p in OPERATION_PARTS]
    if not op_names:
        return "skipped"  # block-level case filed under an ops handler
    assert len(op_names) == 1, f"ambiguous operation parts {op_names}"
    part_name = op_names[0]
    type_name, fn_name = OPERATION_PARTS[part_name]
    state = _read_ssz(case_dir, "pre", spec.BeaconState)
    op = _read_ssz(case_dir, part_name, getattr(spec, type_name))
    process = getattr(spec, fn_name)
    if part_name == "body":
        # stub engine returns the verdict recorded in execution.yaml
        with open(os.path.join(case_dir, "execution.yaml")) as f:
            execution_valid = yaml.safe_load(f)["execution_valid"]

        class _Engine(spec.NoopExecutionEngine):
            def verify_and_notify_new_payload(self, req) -> bool:
                return execution_valid
        run = lambda: process(state, op, _Engine())  # noqa: E731
    else:
        run = lambda: process(state, op)  # noqa: E731
    if "post" in parts:
        run()
        _assert_post(spec, state, case_dir, case_dir)
        return "replayed"
    _expect_rejection(run, case_dir)
    return "replayed"


def _replay_epoch_processing(spec, case_dir: str, parts, meta) -> str:
    sub = meta.get("sub_transition")
    if not sub:
        return "skipped"  # hand-shaped case driving its stage inline
    state = _read_ssz(case_dir, "pre", spec.BeaconState)
    run = lambda: getattr(spec, sub)(state)  # noqa: E731
    if "post" in parts:
        run()
        _assert_post(spec, state, case_dir, case_dir)
        return "replayed"
    _expect_rejection(run, case_dir)
    return "replayed"


def _replay_blocks(spec, case_dir: str, parts, meta) -> str:
    """sanity/blocks and finality: full state_transition runs."""
    state = _read_ssz(case_dir, "pre", spec.BeaconState)
    count = meta.get("blocks_count", 0)
    blocks = [_read_ssz(case_dir, f"blocks_{i}", spec.SignedBeaconBlock)
              for i in range(count)]
    if "post" in parts:
        for block in blocks:
            spec.state_transition(state, block, validate_result=True)
        _assert_post(spec, state, case_dir, case_dir)
        return "replayed"
    if not blocks:
        return "skipped"
    for block in blocks[:-1]:
        spec.state_transition(state, block, validate_result=True)
    _expect_rejection(
        lambda: spec.state_transition(state, blocks[-1],
                                      validate_result=True), case_dir)
    return "replayed"


def _replay_slots(spec, case_dir: str, parts, meta) -> str:
    state = _read_ssz(case_dir, "pre", spec.BeaconState)
    n = int(meta["slots"])
    spec.process_slots(state, state.slot + n)
    _assert_post(spec, state, case_dir, case_dir)
    return "replayed"


def replay_case(case_dir: str, preset: str, fork: str, runner: str,
                handler: str) -> str:
    """Replay one case directory; returns 'replayed' or 'skipped',
    raises :class:`Mismatch` (or a decode error) on infidelity."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.utils import bls

    if os.path.exists(os.path.join(case_dir, "INCOMPLETE")):
        raise Mismatch(f"{case_dir}: INCOMPLETE marker present")
    parts = {f.split(".")[0] for f in os.listdir(case_dir)}
    meta = _read_meta(case_dir)
    spec = build_spec(fork, preset)

    # bls_setting 2 = signatures stubbed/invalid by construction: the
    # vector only reproduces with signature verification off
    old_active = bls.bls_active
    bls.bls_active = meta.get("bls_setting", 0) != 2
    try:
        if runner == "operations":
            return _replay_operations(spec, case_dir, parts, meta)
        if runner == "epoch_processing":
            return _replay_epoch_processing(spec, case_dir, parts, meta)
        if runner == "finality":
            return _replay_blocks(spec, case_dir, parts, meta)
        if runner == "sanity":
            if handler == "slots":
                return _replay_slots(spec, case_dir, parts, meta)
            return _replay_blocks(spec, case_dir, parts, meta)
        return "skipped"
    finally:
        bls.bls_active = old_active


def walk_cases(tree_root: str):
    """Yield (case_dir, preset, fork, runner, handler) for every
    replayable-runner case under ``<tree_root>/tests``."""
    tests_root = os.path.join(tree_root, "tests")
    if not os.path.isdir(tests_root):
        return
    for preset in sorted(os.listdir(tests_root)):
        for fork in sorted(os.listdir(os.path.join(tests_root, preset))):
            fork_dir = os.path.join(tests_root, preset, fork)
            for runner in sorted(os.listdir(fork_dir)):
                if runner not in REPLAYABLE_RUNNERS:
                    continue
                runner_dir = os.path.join(fork_dir, runner)
                for handler in sorted(os.listdir(runner_dir)):
                    handler_dir = os.path.join(runner_dir, handler)
                    for suite in sorted(os.listdir(handler_dir)):
                        suite_dir = os.path.join(handler_dir, suite)
                        for case in sorted(os.listdir(suite_dir)):
                            yield (os.path.join(suite_dir, case),
                                   preset, fork, runner, handler)


def replay_tree(tree_root: str, verbose=False) -> dict:
    """Replay every replayable case; returns the summary dict with any
    mismatches listed under ``"mismatches"``."""
    summary = {"replayed": 0, "skipped": 0, "mismatches": []}
    skips = []
    for case_dir, preset, fork, runner, handler in walk_cases(tree_root):
        try:
            outcome = replay_case(case_dir, preset, fork, runner, handler)
        except Mismatch as exc:
            summary["mismatches"].append(str(exc))
            continue
        except _REJECTIONS as exc:
            # decode failures and unexpected spec rejections are
            # infidelity too, with the exception as the evidence
            summary["mismatches"].append(
                f"{case_dir}: {type(exc).__name__}: {exc}")
            continue
        summary[outcome] += 1
        if outcome == "skipped":
            skips.append(case_dir)
    if verbose:
        for s in skips:
            print(f"  skip (not replayable): {s}")
    return summary


def main(args=None) -> int:
    parser = argparse.ArgumentParser(
        prog="corpus-replay",
        description="Re-execute emitted vectors through the spec and "
                    "verify byte fidelity")
    parser.add_argument("-o", "--output-dir", required=True,
                        help="corpus tree root (the generator -o dir)")
    parser.add_argument("-v", "--verbose", action="store_true")
    ns = parser.parse_args(args)

    # host tool: its fork pool's workers must not contend for a chip
    import jax
    jax.config.update("jax_platforms", "cpu")

    summary = replay_tree(ns.output_dir, verbose=ns.verbose)
    print(f"corpus-check: replayed={summary['replayed']} "
          f"skipped={summary['skipped']} "
          f"mismatches={len(summary['mismatches'])}")
    for m in summary["mismatches"]:
        print(f"  MISMATCH {m}")
    if not summary["replayed"] and not summary["mismatches"]:
        print("corpus-check: nothing replayable found "
              "(wrong --output-dir?)")
        return 1
    return 1 if summary["mismatches"] else 0


if __name__ == "__main__":
    sys.exit(main())
