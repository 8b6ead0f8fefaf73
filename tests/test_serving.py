"""Block-serving pipeline suite (``consensus_specs_tpu/serving``):
pipelined-vs-synchronous byte-identity on captured adversarial load
streams, the fault-injection / flush-failure / corrupt-audit / deadline
fallback legs for the ``serving.pipeline`` site, the one-pairing-per-
window census, chunk-level clone semantics (laziness, the frozen-source
contract, fast-lineage propagation), and the concurrent-head stress
differential (N divergent chunk-level clones vs independent full-copy
replays)."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from consensus_specs_tpu import faults, supervisor
from consensus_specs_tpu.forks import build_spec
from consensus_specs_tpu.serving import BlockServer, clone_state
from consensus_specs_tpu.serving import pipeline
from consensus_specs_tpu.sim import load
from consensus_specs_tpu.test_infra.genesis import create_genesis_state
from consensus_specs_tpu.test_infra.metrics import counting
from consensus_specs_tpu.utils import bls
from consensus_specs_tpu.utils.ssz import hash_tree_root

SITE = "serving.pipeline"

_streams = {}       # scenario name -> LoadStream (built once per session)
_sync_refs = {}     # scenario name -> (digest, results) synchronous oracle


@pytest.fixture(scope="module")
def spec():
    return build_spec("phase0", "minimal")


@pytest.fixture(autouse=True)
def _serving_on(monkeypatch):
    """Pin the engine switch ON regardless of the process env (the CI
    off-leg runs the whole suite under CS_TPU_SERVING=0; off-behavior
    tests override to \"0\" themselves — the switch reads live)."""
    monkeypatch.setenv("CS_TPU_SERVING", "1")


def _stream(spec, name):
    s = _streams.get(name)
    if s is None:
        s = _streams[name] = load.generate(spec, seed=3, name=name)
    return s


def _sync_ref(spec, name):
    """The synchronous oracle for one stream: digest + per-block
    verdicts of a serving-OFF replay, computed once."""
    ref = _sync_refs.get(name)
    if ref is None:
        prev = os.environ.get("CS_TPU_SERVING")
        os.environ["CS_TPU_SERVING"] = "0"
        try:
            store = load.anchor_store(spec, _stream(spec, name))
            results = load.serve(BlockServer(spec, store),
                                 _stream(spec, name))
        finally:
            if prev is None:
                os.environ.pop("CS_TPU_SERVING", None)
            else:
                os.environ["CS_TPU_SERVING"] = prev
        ref = _sync_refs[name] = (load.store_digest(spec, store), results)
    return ref


def _serve_pipelined(spec, name, window=3):
    stream = _stream(spec, name)
    store = load.anchor_store(spec, stream)
    results = load.serve(BlockServer(spec, store, window=window), stream)
    return load.store_digest(spec, store), results


# ---------------------------------------------------------------------------
# lane differential + engine citizenship legs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", load.DEFAULT_MIX)
def test_pipelined_lane_byte_identical_to_sync(spec, name):
    """Window batching + overlapped flush + chunk-level snapshots must
    not move a single byte of consensus state: deep store digests and
    per-block accept/reject verdicts match the synchronous oracle."""
    ref_digest, ref_results = _sync_ref(spec, name)
    with counting() as delta:
        digest, results = _serve_pipelined(spec, name)
    assert digest == ref_digest
    assert results == ref_results
    n_blocks = _stream(spec, name).n_blocks
    assert delta["serving.blocks{path=pipelined}"] == n_blocks
    assert delta["serving.blocks{path=sync}"] == 0
    assert delta["serving.windows"] > 0
    assert delta["serving.clones"] > 0
    assert sum(v for k, v in delta.items()
               if k.startswith("serving.fallbacks")) == 0


def test_serving_off_leg_counts_sync_path(spec, monkeypatch):
    monkeypatch.setenv("CS_TPU_SERVING", "0")
    ref_digest, ref_results = _sync_ref(spec, "equivocation")
    with counting() as delta:
        digest, results = _serve_pipelined(spec, "equivocation")
    assert digest == ref_digest and results == ref_results
    assert delta["serving.blocks{path=sync}"] == \
        _stream(spec, "equivocation").n_blocks
    assert delta["serving.blocks{path=pipelined}"] == 0
    assert delta["serving.windows"] == 0


def test_injected_fault_falls_back_counted(spec):
    """An injected fault at the first window rolls back and replays it
    synchronously — byte-identical result, exactly one counted
    ``reason=injected`` trip, later windows still pipelined."""
    ref_digest, ref_results = _sync_ref(spec, "equivocation")
    sched = faults.FaultSchedule({SITE: [1]})
    with counting() as delta:
        with faults.injected(sched):
            digest, results = _serve_pipelined(spec, "equivocation")
    assert digest == ref_digest and results == ref_results
    assert sched.fully_fired(), (sched.planned, sched.fired)
    assert delta["serving.fallbacks{reason=injected}"] == 1
    assert delta["serving.blocks{path=sync}"] > 0
    assert delta["serving.blocks{path=pipelined}"] > 0


def test_flush_failure_reverifies_synchronously(spec, monkeypatch):
    """A worker-lane flush verdict of False (forced here; organically a
    bad signature) unwinds BOTH in-flight windows at the barrier and
    reverifies per-block — byte-identical, counted ``reason=reverify``,
    zero blocks left on the pipelined series."""
    ref_digest, ref_results = _sync_ref(spec, "equivocation")
    monkeypatch.setattr(pipeline._WindowBatch, "resolve",
                        lambda self: False)
    with counting() as delta:
        digest, results = _serve_pipelined(spec, "equivocation")
    assert digest == ref_digest and results == ref_results
    assert delta["serving.fallbacks{reason=reverify}"] > 0
    assert delta["serving.blocks{path=pipelined}"] == 0
    assert delta["serving.blocks{path=sync}"] == \
        _stream(spec, "equivocation").n_blocks


def test_corrupt_audit_catches_tamper_and_quarantines(
        spec, monkeypatch, tmp_path):
    """Corrupt-mode injection tampers a pipelined post-state; the
    rate-1 sentinel audit at the window barrier must catch the
    divergence, quarantine the site, and serve the rest of the stream
    synchronously — post-drain store still byte-identical."""
    monkeypatch.setenv("CS_TPU_SUPERVISOR", "1")
    monkeypatch.setenv("CS_TPU_AUDIT_RATE", "1")
    monkeypatch.setenv("CS_TPU_BREAKER_THRESHOLD", "1000000000")
    monkeypatch.setenv("CS_TPU_SIM_ARTIFACTS", str(tmp_path))
    supervisor.reset()
    try:
        ref_digest, ref_results = _sync_ref(spec, "equivocation")
        sched = faults.FaultSchedule(corrupt={SITE: [1]})
        with counting() as delta:
            with faults.injected(sched):
                digest, results = _serve_pipelined(spec, "equivocation")
        assert digest == ref_digest and results == ref_results
        assert sched.corrupted, "corrupt injection never armed"
        assert delta[
            "supervisor.audits{result=fail,site=serving.pipeline}"] == 1
        assert delta[
            "supervisor.quarantines{site=serving.pipeline}"] == 1
        assert supervisor.states()[SITE] == "quarantined"
        assert delta["serving.fallbacks{reason=reverify}"] == 1
    finally:
        supervisor.reset()


def test_deadline_falls_back_counted(spec, monkeypatch):
    """A spent per-window deadline budget converts the optimistic pass
    into a counted ``reason=deadline`` synchronous replay."""
    monkeypatch.setenv("CS_TPU_SUPERVISOR", "1")
    monkeypatch.setenv("CS_TPU_DEADLINE_MS", "0.0001")
    monkeypatch.setenv("CS_TPU_BREAKER_THRESHOLD", "1000000000")
    supervisor.reset()
    try:
        ref_digest, ref_results = _sync_ref(spec, "equivocation")
        with counting() as delta:
            digest, results = _serve_pipelined(spec, "equivocation")
        assert digest == ref_digest and results == ref_results
        assert delta["serving.fallbacks{reason=deadline}"] > 0
        assert delta["serving.blocks{path=pipelined}"] == 0
        assert delta["serving.blocks{path=sync}"] == \
            _stream(spec, "equivocation").n_blocks
    finally:
        supervisor.reset()


def test_one_pairing_per_window_census(spec):
    """With real signatures, the window's combined flush must fold to
    EXACTLY one pairing per window — strictly below the sync lane's
    one-per-block count — without moving a byte."""
    if not bls.bls_active:
        pytest.skip("needs --enable-bls (real pairings)")
    name = "equivocation"
    ref_digest, _ = _sync_ref(spec, name)
    bls.clear_verify_memo()
    with counting() as sync_delta:
        os.environ["CS_TPU_SERVING"] = "0"
        try:
            store = load.anchor_store(spec, _stream(spec, name))
            load.serve(BlockServer(spec, store), _stream(spec, name))
        finally:
            os.environ["CS_TPU_SERVING"] = "1"
    bls.clear_verify_memo()
    with counting() as pipe_delta:
        digest, _ = _serve_pipelined(spec, name, window=4)
    assert digest == ref_digest
    windows = pipe_delta["serving.windows"]
    assert windows > 0
    assert pipe_delta["bls.pairings"] == windows, \
        (pipe_delta["bls.pairings"], windows)
    assert sync_delta["bls.pairings"] > pipe_delta["bls.pairings"]


# ---------------------------------------------------------------------------
# chunk-level clones
# ---------------------------------------------------------------------------

def _genesis(spec, n=64):
    return create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * n, spec.MAX_EFFECTIVE_BALANCE)


def test_clone_state_byte_identity_and_isolation(spec):
    state = _genesis(spec)
    base_root = bytes(hash_tree_root(state))
    with counting() as delta:
        cl = clone_state(state)
    assert delta["serving.clones"] == 1
    assert delta["serving.clone_fields{mode=fast}"] > 0
    assert delta["serving.clone_fields{mode=lazy}"] > 0
    assert bytes(hash_tree_root(cl)) == base_root
    # divergent mutation of a fast field and a lazy field: the clone
    # tracks a full copy mutated identically, the source never moves
    ref = state.copy()
    for st in (ref, cl):
        st.balances[1] = st.balances[1] + 7
        st.validators[0].effective_balance = \
            st.validators[0].effective_balance + 1
    mutated = bytes(hash_tree_root(ref))
    assert bytes(hash_tree_root(cl)) == mutated
    assert mutated != base_root
    assert bytes(hash_tree_root(state)) == base_root


def test_lazy_clone_defers_until_touched(spec):
    state = _genesis(spec)
    with counting() as delta:
        cl = clone_state(state)
    assert delta["serving.materializations{stage=items}"] == 0, \
        "clone_state paid the per-element walk up front"
    with counting() as delta:
        cl.validators[0]                      # first touch materializes
    assert delta["serving.materializations{stage=items}"] == 1
    with counting() as delta:
        cl.validators[1]
    assert delta["serving.materializations{stage=items}"] == 0


def test_lazy_clone_copies_registry_elements_like_copy(spec):
    """A materialized registry holds what ``copy()`` would: equal
    fields and memoized roots, each element owned by the clone at its
    index; a write through one moves neither the source nor its root."""
    state = _genesis(spec)
    root = bytes(hash_tree_root(state))
    cl = clone_state(state)
    src, got = state.validators, cl.validators
    for i in range(len(src)):
        a, b = src[i], got[i]
        assert a is not b and type(a) is type(b)
        assert all(getattr(a, f) == getattr(b, f) for f in type(a)._fields)
        assert object.__getattribute__(b, "_root_cache") == \
            object.__getattribute__(a, "_root_cache")
        assert b._owner[0]() is got and b._owner[1] == i
    ref = state.copy()
    for st in (ref, cl):
        st.validators[3].slashed = True
    assert not src[3].slashed
    assert bytes(hash_tree_root(state)) == root
    assert bytes(hash_tree_root(cl)) == bytes(hash_tree_root(ref)) != root


def test_lazy_clone_frozen_source_contract(spec):
    """Mutating the source after a chunk-level clone must fail the
    clone's deferred touches loudly — never materialize drifted data."""
    state = _genesis(spec)
    cl = clone_state(state)
    state.validators[0].effective_balance = \
        state.validators[0].effective_balance + 1
    with pytest.raises(RuntimeError, match="frozen"):
        cl.validators[0]
    # a clone taken from the new (post-mutation) source state is fine
    assert bytes(hash_tree_root(clone_state(state))) == \
        bytes(hash_tree_root(state))


def test_fast_clone_lineage_stays_fast(spec):
    """``copy()`` of a cloned state's immutable-element sequences must
    stay on the C-level fast path through the whole lineage (fork
    choice copies snapshots of snapshots)."""
    state = _genesis(spec)
    cl = clone_state(state)
    assert getattr(type(cl.balances), "_serving_fast", False)
    with counting() as delta:
        again = cl.balances.copy()
    assert delta["serving.clone_fields{mode=fast}"] == 1
    assert getattr(type(again), "_serving_fast", False)
    assert type(again) is type(cl.balances)    # no subclass nesting
    assert list(again) == list(cl.balances)


# ---------------------------------------------------------------------------
# concurrent-head stress: N divergent clones vs independent replays
# ---------------------------------------------------------------------------

def _concurrent_heads(spec, n_validators, replays):
    slots_per_epoch = int(spec.SLOTS_PER_EPOCH)
    state = _genesis(spec, n_validators)
    spec.process_slots(state, slots_per_epoch)
    base_root = bytes(hash_tree_root(state))
    half = int(spec.MAX_EFFECTIVE_BALANCE) // 2

    with counting() as delta:
        clones = [clone_state(state) for _ in range(replays)]
    assert delta["serving.clones"] == replays
    assert delta["serving.materializations{stage=items}"] == 0

    cloned_roots = []
    for k, st in enumerate(clones):
        st.balances[k % n_validators] = half + k
        spec.process_slots(st, int(st.slot) + slots_per_epoch)
        cloned_roots.append(bytes(hash_tree_root(st)))

    independent_roots = []
    for k in range(replays):
        st = state.copy()
        st.balances[k % n_validators] = half + k
        spec.process_slots(st, int(st.slot) + slots_per_epoch)
        independent_roots.append(bytes(hash_tree_root(st)))

    assert cloned_roots == independent_roots, \
        "a divergently-advanced chunk-level clone diverged from its " \
        "independent full-copy replay"
    assert len(set(cloned_roots)) == replays   # heads really diverged
    assert bytes(hash_tree_root(state)) == base_root, \
        "advancing clones disturbed the shared base snapshot"


def test_concurrent_heads_divergent_clones(spec):
    _concurrent_heads(spec, n_validators=256, replays=4)


@pytest.mark.slow
def test_concurrent_heads_divergent_clones_1m():
    """The ISSUE-scale leg: divergent heads off one 1M-column state."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks"))
    from bench_state_arrays import build_state
    spec = build_spec("altair", "minimal")
    slots_per_epoch = int(spec.SLOTS_PER_EPOCH)
    state = build_state(spec, 1 << 20)
    spec.process_slots(state, slots_per_epoch)
    base_root = bytes(hash_tree_root(state))
    clones = [clone_state(state) for _ in range(4)]
    roots = []
    for k, st in enumerate(clones):
        st.balances[k] = st.balances[k] - (k + 1)
        spec.process_slots(st, int(st.slot) + slots_per_epoch)
        roots.append(bytes(hash_tree_root(st)))
    for k in range(4):
        st = state.copy()
        st.balances[k] = st.balances[k] - (k + 1)
        spec.process_slots(st, int(st.slot) + slots_per_epoch)
        assert bytes(hash_tree_root(st)) == roots[k]
    assert bytes(hash_tree_root(state)) == base_root


# ---------------------------------------------------------------------------
# causal tracing + flight recorder over the pipeline
# ---------------------------------------------------------------------------

def _walk_assert_no_orphans(name, node):
    assert "orphan" not in node, f"orphan-flagged span: {name}"
    for child_name, child in node.get("children", {}).items():
        _walk_assert_no_orphans(child_name, child)


@pytest.fixture()
def _traced():
    from consensus_specs_tpu.obs import tracing
    tracing.enable(True, counters=False)
    tracing.reset()
    yield tracing
    tracing.enable(False)
    tracing.reset()


def test_pipelined_replay_one_causal_tree_per_window(spec, _traced):
    """Acceptance: under tracing, a pipelined replay yields ONE
    causally-linked tree per window — the worker-lane flush and the
    barrier join are CHILDREN of their window's span, never disjoint
    roots, and nothing is orphan-flagged."""
    stream = _stream(spec, "equivocation")       # built before tracing
    _traced.reset()
    store = load.anchor_store(spec, stream)
    server = BlockServer(spec, store, window=3)
    load.serve(server, stream)
    tree = _traced.span_tree()
    win = tree["serving.window"]
    n_windows = win["count"]
    assert n_windows > 0
    assert win["children"]["serving.flush"]["count"] == n_windows
    assert win["children"]["serving.barrier"]["count"] == n_windows
    # no disjoint roots for the cross-thread legs
    assert "serving.flush" not in tree
    assert "serving.barrier" not in tree
    for name, node in tree.items():
        _walk_assert_no_orphans(name, node)
    # the per-window latency log carries one entry per window with
    # distinct trace ids and the span-aligned stats
    log = server.window_log
    assert len(log) == n_windows
    ids = [e["trace_id"] for e in log]
    assert len(set(ids)) == len(ids) and None not in ids
    for entry in log:
        assert entry["outcome"] == "pipelined"
        for key in ("queued_s", "optimistic_s", "flush_s", "barrier_s"):
            assert entry[key] >= 0.0


def test_replayed_window_keeps_causal_tree(spec, _traced, monkeypatch):
    """A window whose worker-lane flush fails replays synchronously at
    the barrier — still inside the window's trace (span
    ``serving.replay``), logged with ``outcome=replayed``."""
    stream = _stream(spec, "equivocation")
    _traced.reset()
    monkeypatch.setattr(pipeline._WindowBatch, "resolve",
                        lambda self: False)
    store = load.anchor_store(spec, stream)
    server = BlockServer(spec, store, window=3)
    load.serve(server, stream)
    tree = _traced.span_tree()
    win = tree["serving.window"]
    assert win["children"]["serving.replay"]["count"] >= 1
    assert "serving.replay" not in tree
    replayed = [e for e in server.window_log
                if e["outcome"] == "replayed"]
    assert len(replayed) >= 1
    assert all(e["replay_s"] >= 0.0 for e in replayed)


def test_untraced_replay_logs_windows_without_ids(spec):
    """Tracing off: the latency log still accumulates (stats cost a
    few clocks), trace ids are None — no context machinery engaged."""
    stream = _stream(spec, "equivocation")
    store = load.anchor_store(spec, stream)
    server = BlockServer(spec, store, window=3)
    load.serve(server, stream)
    assert server.window_log
    assert all(e["trace_id"] is None for e in server.window_log)


def test_lost_context_windows_flagged_as_orphans(spec, _traced,
                                                monkeypatch):
    """Satellite regression: if window submission loses its captured
    context (capture_context returning None), the worker-lane spans
    must surface as FLAGGED orphan roots in the tree and the rendered
    report — never silently merge into an unrelated tree."""
    from consensus_specs_tpu.obs import export, tracing
    monkeypatch.setattr(tracing, "capture_context", lambda: None)
    stream = _stream(spec, "equivocation")
    _traced.reset()
    store = load.anchor_store(spec, stream)
    server = BlockServer(spec, store, window=3)
    load.serve(server, stream)
    tree = _traced.span_tree()
    assert tree["serving.flush"]["orphan"] is True
    assert "serving.flush" not in tree["serving.window"]["children"]
    assert "[orphan thread]" in export.report()
    assert all(e["trace_id"] is None for e in server.window_log)


def test_quarantine_artifact_carries_flight_dump(spec, monkeypatch,
                                                 tmp_path):
    """Acceptance: a forced quarantine's artifact embeds a non-empty
    flight dump (the last-N-events tail, flush-worker lane included)
    in the format ``sim.repro`` prints before replaying."""
    from consensus_specs_tpu.obs import flight
    monkeypatch.setenv("CS_TPU_SUPERVISOR", "1")
    monkeypatch.setenv("CS_TPU_AUDIT_RATE", "1")
    monkeypatch.setenv("CS_TPU_BREAKER_THRESHOLD", "1000000000")
    monkeypatch.setenv("CS_TPU_SIM_ARTIFACTS", str(tmp_path))
    supervisor.reset()
    flight.reset(refresh_env=True)
    flight.enable(True)
    try:
        sched = faults.FaultSchedule(corrupt={SITE: [1]})
        with faults.injected(sched):
            _serve_pipelined(spec, "equivocation")
        assert supervisor.states()[SITE] == "quarantined"
        path = supervisor.last_quarantine()
        assert path and os.path.exists(path)
        with open(path) as f:
            payload = json.load(f)
        dump = payload["flight"]
        assert dump["trigger"] == "quarantine"
        assert dump["threads"], "quarantine artifact flight dump empty"
        assert any(recs for recs in dump["threads"].values())
        # the windows the pipeline submitted are in the tail
        codes = [r[2] for recs in dump["threads"].values()
                 for r in recs]
        assert "window" in codes and "breaker" in codes
        text = flight.format_dump(dump)
        assert "quarantine" in text
    finally:
        supervisor.reset()
        flight.reset(refresh_env=True)


def test_flight_dump_deterministic_across_seeded_replays(spec):
    """Two identical seeded replays leave identical flight tails
    (codes + details per thread role; sequence numbers and wall-clock
    stripped) — the dump is replay evidence, not noise."""
    from consensus_specs_tpu.obs import flight, tracing

    def one_tail():
        flight.reset()
        flight.enable(True)
        tracing.enable(True, counters=False)
        tracing.reset()
        try:
            _serve_pipelined(spec, "equivocation")
            d = flight.dump(trigger="manual")
            # normalize: thread NAMES differ per run (thread counter),
            # so key by role = records observed on main vs worker
            return {
                "main": [(r[2], r[3]) for r in
                         d["threads"].get("MainThread", [])],
                "workers": sorted(
                    tuple((r[2], r[3]) for r in recs)
                    for name, recs in d["threads"].items()
                    if name != "MainThread"),
            }
        finally:
            tracing.enable(False)
            tracing.reset()
            flight.enable(False)

    first, second = one_tail(), one_tail()
    assert first["main"] and first["workers"]
    assert first == second
    flight.reset(refresh_env=True)
