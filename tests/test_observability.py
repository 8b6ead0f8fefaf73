"""Unified telemetry subsystem tests (``consensus_specs_tpu/obs``):
registry semantics, span-tree shape on a real replay, exporter golden
checks, and the counter-diff fixture attributing engine-on vs
engine-off paths to different labels."""
import json

import pytest

from consensus_specs_tpu import obs
from consensus_specs_tpu.obs import export, registry, tracing
from consensus_specs_tpu.test_infra.metrics import counting
from consensus_specs_tpu.utils import env_flags


@pytest.fixture(autouse=True)
def _quiet_spans():
    """Spans off around every test here (individual tests enable as
    needed); teardown restores the env-derived gate state so a
    CS_TPU_PROFILE=1 pytest process keeps tracing the suites collected
    after this module."""
    tracing.enable(False)
    tracing.reset()
    yield
    tracing.enable(env_flags.PROFILE or env_flags.TRACE,
                   counters=env_flags.TRACE)
    tracing.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_labels_and_identity():
    c = registry.counter("t.obs.requests")
    assert registry.counter("t.obs.requests") is c
    a = c.labels(path="engine")
    b = c.labels(path="spec")
    assert c.labels(path="engine") is a        # bound series are stable
    a.add()
    a.add(2)
    b.add(5)
    assert c.value(path="engine") == 3
    assert c.value(path="spec") == 5
    assert c.total() == 8
    # label order does not split series
    c2 = registry.counter("t.obs.multi")
    c2.inc(a="1", b="2")
    assert c2.labels(b="2", a="1").n == 1


def test_metric_kind_conflict_raises():
    registry.counter("t.obs.kind")
    with pytest.raises(TypeError):
        registry.gauge("t.obs.kind")


def test_reset_keeps_bound_series_live():
    c = registry.counter("t.obs.reset")
    s = c.labels(backend="x")
    s.add(7)
    registry.reset("t.obs.")
    assert s.n == 0
    s.add()                                    # the old handle still counts
    assert c.value(backend="x") == 1


def test_prefix_reset_scopes():
    a = registry.counter("t.scope.a").labels()
    b = registry.counter("t.other.b").labels()
    a.add(3)
    b.add(4)
    registry.reset("t.scope.")
    assert a.n == 0 and b.n == 4


def test_snapshot_isolation():
    c = registry.counter("t.obs.iso")
    c.labels(k="v").add(2)
    snap = registry.snapshot()
    snap["t.obs.iso"]["series"]["{k=v}"] = 999
    snap["t.obs.iso"]["type"] = "gauge"
    fresh = registry.snapshot()
    assert fresh["t.obs.iso"]["series"]["{k=v}"] == 2
    assert fresh["t.obs.iso"]["type"] == "counter"


def test_gauge_set_and_max():
    g = registry.gauge("t.obs.gauge")
    g.set(5, lane="a")
    g.labels(lane="a").set_max(3)
    assert g.value(lane="a") == 5
    g.labels(lane="a").set_max(9)
    assert g.value(lane="a") == 9


def test_histogram_buckets():
    h = registry.histogram("t.obs.hist", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    val = h.labels()._value()
    assert val["count"] == 4
    assert val["min"] == 0.05 and val["max"] == 5.0
    assert val["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 1}


def test_histogram_quantile_summaries():
    h = registry.histogram("t.obs.quant", buckets=(0.1, 1.0))
    empty = h.labels()._value()
    assert empty["p50"] is None and empty["p90"] is None \
        and empty["p99"] is None
    for v in (0.05, 0.2, 0.4, 0.9, 5.0):
        h.observe(v)
    val = h.labels()._value()
    assert val["min"] <= val["p50"] <= val["p90"] <= val["p99"] <= val["max"]
    # p50 interpolates within its landing bucket; p99 within the
    # overflow bucket, sharpened toward the tracked max
    assert 0.1 <= val["p50"] <= 1.0
    assert 1.0 <= val["p99"] <= 5.0


def test_histogram_quantile_single_observation_collapses():
    h = registry.histogram("t.obs.quant1", buckets=(1.0,))
    h.observe(0.3)
    val = h.labels()._value()
    assert val["p50"] == val["p90"] == val["p99"] == 0.3


def test_counting_delta_missing_keys_read_zero():
    c = registry.counter("t.obs.delta").labels()
    with counting() as delta:
        c.add(3)
    assert delta["t.obs.delta"] == 3
    assert delta["t.obs.never_bumped"] == 0
    assert delta.nonzero().get("t.obs.delta") == 3


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_disabled_spans_record_nothing():
    with tracing.span("t.span.off"):
        pass
    assert tracing.stats() == {}
    assert tracing.span_tree() == {}


def test_nested_spans_self_vs_cumulative():
    tracing.enable(True, counters=False)
    with tracing.span("outer"):
        for _ in range(3):
            with tracing.span("inner"):
                pass
    st = tracing.stats()
    assert st["outer"]["count"] == 1
    assert st["inner"]["count"] == 3
    # cumulative >= self; the parent's self excludes child time, so the
    # self column sums to <= wall-clock (the nesting double-count fix)
    assert st["outer"]["total_s"] >= st["outer"]["self_s"]
    assert abs(st["outer"]["self_s"]
               + st["inner"]["total_s"] - st["outer"]["total_s"]) < 1e-3
    tree = tracing.span_tree()
    assert tree["outer"]["children"]["inner"]["count"] == 3


def test_span_counter_deltas_attach():
    c = registry.counter("t.span.work").labels(kind="unit")
    tracing.enable(True, counters=True)
    with tracing.span("t.span.cd"):
        c.add(4)
    node = tracing.span_tree()["t.span.cd"]
    assert node["counters"]["t.span.work{kind=unit}"] == 4


def test_span_exception_still_recorded():
    tracing.enable(True, counters=False)
    with pytest.raises(ValueError):
        with tracing.span("t.span.err"):
            raise ValueError("boom")
    assert tracing.stats()["t.span.err"]["count"] == 1


def _profiled_span_events(trace_dir, enabled):
    """Nested ``t.prof.*`` spans inside a CPU profiler session; returns
    the trace's host events of those names as (line, name, start, end)."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tracing.enable(enabled, counters=False)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with tracing.span("t.prof.outer"):
            with tracing.span("t.prof.inner"):
                sum(range(1000))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    return [(line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("t.prof.")]


def test_enabled_spans_land_nested_in_a_profiler_trace(tmp_path):
    events = _profiled_span_events(tmp_path, enabled=True)
    by_name = {name: (line, s, e) for line, name, s, e in events}
    assert len(events) == 2 and set(by_name) == {"t.prof.outer",
                                                 "t.prof.inner"}
    outer, inner = by_name["t.prof.outer"], by_name["t.prof.inner"]
    assert outer[0] == inner[0]             # the calling thread's line
    assert outer[1] <= inner[1] < inner[2] <= outer[2]
    assert tracing.stats()["t.prof.inner"]["count"] == 1


def test_disabled_spans_write_no_profiler_events(tmp_path):
    assert _profiled_span_events(tmp_path, enabled=False) == []


def test_enabled_spans_need_no_jax():
    """``obs`` never imports jax itself: an enabled span in a process
    without jax records its time and loads nothing."""
    import subprocess
    import sys
    code = ("import sys\n"
            "from consensus_specs_tpu.obs import tracing\n"
            "tracing.enable(True)\n"
            "with tracing.span('t.nojax'):\n"
            "    pass\n"
            "assert tracing.stats()['t.nojax']['count'] == 1\n"
            "assert 'jax' not in sys.modules\n")
    repo = __file__.rsplit("/tests/", 1)[0]
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]


# ---------------------------------------------------------------------------
# real replay: span-tree shape + label attribution
# ---------------------------------------------------------------------------

def _replayed_snapshot(slots=8, validators=32):
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.tools.obs_report import build_state, replay
    from consensus_specs_tpu.utils import bls
    spec = build_spec("phase0", "minimal")
    state = build_state(spec, validators)
    was_active = bls.bls_active
    bls.bls_active = False
    obs.reset_all()
    obs.enable(True, counters=True)
    try:
        replay(spec, state, slots)
    finally:
        obs.enable(False)
        bls.bls_active = was_active
    return export.snapshot()


def test_state_transition_span_tree_shape():
    snap = _replayed_snapshot()
    tree = snap["spans"]
    st = tree["st.state_transition"]
    assert st["count"] == 8
    slots_node = st["children"]["st.process_slots"]
    assert slots_node["count"] == 8
    assert "st.process_slot" in slots_node["children"]
    assert "st.process_epoch" in slots_node["children"]
    assert "st.process_block" in st["children"]
    # the batched merkleization shows up inside the state-root spans
    slot_root = slots_node["children"]["st.process_slot"]["children"][
        "st.state_root"]
    assert "hash_forest.flush" in slot_root["children"] \
        or "hash_forest.flush" in st["children"]["st.state_root"]["children"]
    # fork-choice handlers traced too (replay feeds a store)
    assert tree["on_block"]["count"] == 8
    # per-span counter deltas attached under CS_TPU_TRACE semantics
    assert any(st["counters"].values())


def test_replay_snapshot_has_labeled_engine_counters():
    snap = _replayed_snapshot()
    metrics = snap["metrics"]
    pairs = metrics["merkle.pairs_hashed"]["series"]
    assert sum(pairs.values()) > 0
    assert set(pairs) <= {"{backend=native}", "{backend=jax}",
                          "{backend=hashlib}"}
    heads = metrics["forkchoice.head"]["series"]
    assert sum(heads.values()) == 8
    epochs = metrics["epoch.transition"]["series"]
    assert sum(epochs.values()) > 0
    assert metrics["cache.hit"]["series"]["{cache=root}"] > 0
    # the state-arrays store answered the replay's column reads: its
    # cache series populate and every extraction is column-attributed
    sa_series = metrics["state_arrays.extracts"]["series"]
    assert set(sa_series) <= {"{column=registry}", "{column=balances}",
                              "{column=inactivity_scores}",
                              "{column=participation}"}
    assert metrics["cache.hit"]["series"].get("{cache=state_arrays}", 0) > 0
    # (an 8-slot replay only crosses the genesis-epoch transition, which
    # writes nothing — the commit census lives in bench_state_arrays)
    assert "state_arrays.commits" in metrics
    assert not export.schema_problems(snap)


def test_engine_on_vs_off_attribute_to_different_labels():
    """The counter-diff fixture regression: the same epoch transition
    books under path=vectorized with the engine on and path=loop with
    the engine off."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.ops import epoch_kernels as ek
    from consensus_specs_tpu.test_infra.block import next_epoch
    from consensus_specs_tpu.test_infra.genesis import create_genesis_state
    from consensus_specs_tpu.utils import bls
    spec = build_spec("phase0", "minimal")
    was_active = bls.bls_active
    bls.bls_active = False
    try:
        state = create_genesis_state(
            spec, [spec.MAX_EFFECTIVE_BALANCE] * 32,
            spec.MAX_EFFECTIVE_BALANCE)
        next_epoch(spec, state)
        s_on, s_off = state.copy(), state.copy()
        ek.use_vectorized()
        try:
            with counting() as delta_on:
                spec.process_epoch(s_on)
        finally:
            ek.use_loops()
        try:
            with counting() as delta_off:
                spec.process_epoch(s_off)
        finally:
            ek.use_auto()
    finally:
        bls.bls_active = was_active
    assert delta_on["epoch.transition{path=vectorized}"] > 0
    assert delta_on["epoch.transition{path=loop}"] == 0
    assert delta_off["epoch.transition{path=vectorized}"] == 0
    assert delta_off["epoch.transition{path=loop}"] > 0


def test_metrics_diff_fixture(metrics_diff):
    c = registry.counter("t.obs.fixture").labels()
    with metrics_diff() as delta:
        c.add(2)
    assert delta["t.obs.fixture"] == 2


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_export_format():
    registry.counter("t.prom.hits").labels(backend="native").add(3)
    registry.gauge("t.prom.depth").set(7)
    registry.histogram("t.prom.lat", buckets=(1.0,)).observe(0.5)
    text = export.to_prometheus()
    assert "# TYPE cs_tpu_t_prom_hits counter" in text
    assert 'cs_tpu_t_prom_hits{backend="native"} 3' in text
    assert "# TYPE cs_tpu_t_prom_depth gauge" in text
    assert "cs_tpu_t_prom_depth 7" in text
    assert 'cs_tpu_t_prom_lat_bucket{le="1.0"} 1' in text
    # buckets are cumulative in the exposition: +Inf must equal _count
    assert 'cs_tpu_t_prom_lat_bucket{le="+Inf"} 1' in text
    assert "cs_tpu_t_prom_lat_count 1" in text
    # per-q quantile gauge lines (single observation collapses all
    # three to the observed value)
    for q in ("0.5", "0.9", "0.99"):
        assert f'cs_tpu_t_prom_lat_quantile{{q="{q}"}} 0.5' in text


def test_json_snapshot_round_trips():
    registry.counter("t.json.c").labels(x="y").add(1)
    parsed = json.loads(export.to_json())
    assert parsed["metrics"]["t.json.c"]["series"]["{x=y}"] == 1
    assert "spans" in parsed and "flags" in parsed


def test_schema_check_accepts_real_and_rejects_corrupt():
    snap = export.snapshot()
    assert export.schema_problems(snap) == []
    bad = json.loads(json.dumps(snap))
    bad["metrics"]["broken"] = {"type": "wat", "series": {"oops": "nan"}}
    probs = export.schema_problems(bad)
    assert any("unknown type" in p for p in probs)
    assert any("non-numeric" in p for p in probs)
    assert export.schema_problems({"metrics": 3}) != []
    with pytest.raises(AssertionError):
        export.assert_schema(snap, require_nonempty=("no.such.metric",))


def test_schema_flags_quantile_violations():
    registry.histogram("t.schema.q", buckets=(1.0,)).observe(0.5)
    bad = json.loads(json.dumps(export.snapshot()))
    v = bad["metrics"]["t.schema.q"]["series"][""]
    v["p50"] = None
    assert any("missing quantile" in p for p in export.schema_problems(bad))
    v["p50"] = 99.0
    assert any("quantile ordering" in p
               for p in export.schema_problems(bad))


def test_report_includes_quantile_columns():
    registry.histogram("t.report.q").observe(0.25)
    text = export.report()
    assert "p50=" in text and "p99=" in text


def test_report_renders_tree_and_metrics():
    registry.counter("t.report.c").labels().add(2)
    tracing.enable(True, counters=False)
    with tracing.span("t.report.outer"):
        with tracing.span("t.report.inner"):
            pass
    text = export.report()
    assert "t.report.outer" in text
    assert "  t.report.inner" in text       # indented under its parent
    assert "t.report.c" in text


# ---------------------------------------------------------------------------
# env gates
# ---------------------------------------------------------------------------

def test_env_flags_registered():
    assert hasattr(env_flags, "PROFILE")
    assert hasattr(env_flags, "TRACE")
    assert hasattr(env_flags, "STATE_ARRAYS")


def test_state_arrays_commit_span_recorded():
    """The deferred column flush books a ``state_arrays.commit`` span
    (profiling on) and a ``state_arrays.commits`` counter tick."""
    import numpy as np
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.state import arrays
    from consensus_specs_tpu.test_infra.genesis import create_genesis_state
    spec = build_spec("phase0", "minimal")
    state = create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * 32, spec.MAX_EFFECTIVE_BALANCE)
    arrays.use_arrays()
    tracing.enable(True)
    try:
        with counting() as delta:
            sa = arrays.of(state)
            sa.set_balances(sa.balances() + np.uint64(1))
        assert delta["state_arrays.commits"] == 1
        assert tracing.stats()["state_arrays.commit"]["count"] == 1
    finally:
        tracing.enable(False)
        arrays.use_auto()


# ---------------------------------------------------------------------------
# reason-labeled fallback accounting (the fault-injection contract)
# ---------------------------------------------------------------------------

def test_fallback_counters_carry_reason_labels():
    """Injected vs organic fallbacks must stay distinguishable in
    ``obs_report``: every engine fallback counter is reason-labeled,
    the full label set is pre-bound at engine import, and no unlabeled
    twin series exists for the harness to miscount into."""
    # importing the engines binds their series at module scope
    import consensus_specs_tpu.forkchoice.proto_array  # noqa: F401
    import consensus_specs_tpu.ops.epoch_kernels  # noqa: F401
    import consensus_specs_tpu.parallel.mesh_epoch  # noqa: F401
    import consensus_specs_tpu.parallel.mesh_merkle  # noqa: F401
    import consensus_specs_tpu.recovery.checkpoint  # noqa: F401
    import consensus_specs_tpu.state.arrays  # noqa: F401
    import consensus_specs_tpu.utils.bls  # noqa: F401
    import consensus_specs_tpu.utils.ssz.merkle  # noqa: F401

    assert set(registry.counter("forkchoice.fallbacks").series_values()) \
        == {"{reason=guard}", "{reason=injected}", "{reason=deadline}"}
    assert set(registry.counter("epoch.fallbacks").series_values()) \
        == {"{reason=guard}", "{reason=injected}", "{reason=deadline}"}
    # engines whose fast path has no organic guard: injected + deadline
    assert set(registry.counter("merkle.fallbacks").series_values()) \
        == {"{reason=injected}", "{reason=deadline}"}
    assert set(registry.counter("state_arrays.fallbacks").series_values()) \
        == {"{reason=injected}", "{reason=deadline}"}
    # the mesh epoch engine declines organically (guards); the merkle
    # leaf-span path has no organic guard of its own; both re-shard
    # elastically on a device loss (counted reason=device_loss)
    assert set(registry.counter("mesh.epoch.fallbacks").series_values()) \
        == {"{reason=guard}", "{reason=injected}", "{reason=deadline}",
            "{reason=device_loss}"}
    assert set(registry.counter("mesh.merkle.fallbacks").series_values()) \
        == {"{reason=injected}", "{reason=deadline}",
            "{reason=device_loss}"}
    # the durability subsystem: injected/deadline skip a checkpoint,
    # io is the organic rung, the rest name recovery-ladder rungs
    assert set(registry.counter("recovery.fallbacks").series_values()) \
        == {"{reason=injected}", "{reason=deadline}", "{reason=io}",
            "{reason=manifest}", "{reason=blob}",
            "{reason=journal_corrupt}", "{reason=torn_record}",
            "{reason=divergence}"}
    assert set(registry.counter("recovery.checkpoints").series_values()) \
        == {"{result=saved}", "{result=skipped}", "{result=refused}"}
    assert set(registry.counter("recovery.restores").series_values()) \
        == {"{path=checkpoint}", "{path=genesis}"}
    flush = set(registry.counter("bls.flush").series_values())
    assert {"{path=fallback,reason=bisect}",
            "{path=fallback,reason=injected}",
            "{path=fallback,reason=deadline}"} <= flush
    assert "{path=fallback}" not in flush


def test_injected_fault_books_injected_reason_only():
    """``faults.count_fallback`` routes an InjectedFault to the
    ``reason=injected`` series and anything else to the organic one —
    an injected trip must never hide in the guard noise."""
    from consensus_specs_tpu import faults
    series = {
        "guard": registry.counter("test.fallbacks").labels(reason="guard"),
        "injected": registry.counter(
            "test.fallbacks").labels(reason="injected"),
    }
    with counting() as delta:
        faults.count_fallback(series, faults.InjectedFault("test.site", 1))
        faults.count_fallback(series, RuntimeError("organic trip"))
        faults.count_fallback(series, None)
    assert delta["test.fallbacks{reason=injected}"] == 1
    assert delta["test.fallbacks{reason=guard}"] == 2


def test_gen_runner_case_errors_are_obs_accounted():
    """The generator's narrowed per-case handler books swallowed
    failures on ``gen.case_errors{error=...}`` instead of vanishing
    them (a fault-injection run must not disappear into a catch-all —
    InjectedFault, a BaseException, escapes it entirely)."""
    from consensus_specs_tpu import faults
    from consensus_specs_tpu.gen import gen_runner

    class _Case:
        preset_name = "minimal"
        fork_name = "phase0"

        def __init__(self, fn):
            self.case_fn = fn
            self.exec_fork = "phase0"

        def dir_path(self):
            return "minimal/phase0/test/test/test/case"

    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        log = []
        with counting() as delta:
            result, _elapsed = gen_runner.generate_test_vector(
                _Case(lambda: (_ for _ in ()).throw(
                    AssertionError("spec invalidity"))), tmp, log)
        assert result == "error"
        assert len(log) == 1
        assert delta["gen.case_errors{error=AssertionError}"] == 1
        # an injected fault is NOT swallowed: it kills the case loudly
        with pytest.raises(faults.InjectedFault):
            gen_runner.generate_test_vector(
                _Case(lambda: (_ for _ in ()).throw(
                    faults.InjectedFault("bls.flush", 1))), tmp, [])


# ---------------------------------------------------------------------------
# cross-thread trace context (obs.tracing.capture_context/adopt_context)
# ---------------------------------------------------------------------------

def test_capture_context_disabled_returns_none():
    assert tracing.capture_context() is None
    # adopting a None context is a no-op (the disabled fast path), not
    # an error — callers never branch on the gate themselves
    with tracing.adopt_context(None):
        pass
    assert tracing.span_tree() == {}


def test_adopted_worker_spans_join_the_request_tree():
    import threading
    tracing.enable(True, counters=False)
    with tracing.span("req"):
        ctx = tracing.capture_context()
        assert ctx is not None and ctx.trace_id >= 1

        def _work():
            with tracing.adopt_context(ctx):
                with tracing.span("work"):
                    pass

        t = threading.Thread(target=_work)
        t.start()
        t.join()
    tree = tracing.span_tree()
    # ONE causal tree: the worker span is a child of the request span,
    # not a disjoint root, and nothing is orphan-flagged
    assert tree["req"]["children"]["work"]["count"] == 1
    assert "work" not in tree
    assert "orphan" not in tree["req"]
    assert "orphan" not in tree["req"]["children"]["work"]


def test_adopt_context_exception_unwinds_cleanly():
    import threading
    tracing.enable(True, counters=False)
    caught = []
    with tracing.span("req"):
        ctx = tracing.capture_context()

        def _work():
            try:
                with tracing.adopt_context(ctx):
                    with tracing.span("boom"):
                        raise ValueError("worker failure")
            except ValueError:
                caught.append(True)

        t = threading.Thread(target=_work)
        t.start()
        t.join()
    assert caught == [True]
    tree = tracing.span_tree()
    assert tree["req"]["children"]["boom"]["count"] == 1
    assert "boom" not in tree


def test_adopt_context_pops_leaked_spans():
    """A worker that hand-enters a span inside ``adopt_context`` and
    never exits it must not poison the thread's stack: the adopt exit
    pops every frame above (and including) the adopted node."""
    import threading
    tracing.enable(True, counters=False)
    with tracing.span("req"):
        ctx = tracing.capture_context()

        def _work():
            with tracing.adopt_context(ctx):
                leaked = tracing.span("leaked")
                leaked.__enter__()          # deliberately never exited
            # stack healed: a fresh span roots at the worker's own root
            # (an orphan, since this thread holds no context now)
            with tracing.span("after"):
                pass

        t = threading.Thread(target=_work)
        t.start()
        t.join()
    tree = tracing.span_tree()
    assert "leaked" in tree["req"]["children"]
    assert tree["after"]["orphan"] is True
    assert "after" not in tree["req"]["children"]


def test_double_adopt_same_thread_refused():
    tracing.enable(True, counters=False)
    with tracing.span("req"):
        ctx = tracing.capture_context()
        with tracing.adopt_context(ctx):
            with pytest.raises(RuntimeError, match="double-adopt"):
                with tracing.adopt_context(ctx):
                    pass
        # the refusal must not have broken the outer adoption: the
        # stack still carries the request node
        with tracing.span("again"):
            pass
    tree = tracing.span_tree()
    assert "again" in tree["req"]["children"]


def test_nested_adoption_of_inner_span_context():
    """Capturing deeper inside the tree parents worker spans at that
    depth, not at the root."""
    import threading
    tracing.enable(True, counters=False)
    with tracing.span("outer"):
        with tracing.span("inner"):
            ctx = tracing.capture_context()

            def _work():
                with tracing.adopt_context(ctx), tracing.span("deep"):
                    pass

            t = threading.Thread(target=_work)
            t.start()
            t.join()
    tree = tracing.span_tree()
    inner = tree["outer"]["children"]["inner"]
    assert inner["children"]["deep"]["count"] == 1


def test_orphan_thread_spans_flagged_in_tree_and_report():
    """Satellite regression: a thread that opens spans WITHOUT adopting
    a context roots a flagged ``[orphan thread]`` tree — visible, never
    silently merged with the main tree."""
    import threading
    tracing.enable(True, counters=False)
    with tracing.span("main.work"):
        pass

    def _work():
        with tracing.span("stray"):
            pass

    t = threading.Thread(target=_work)
    t.start()
    t.join()
    tree = tracing.span_tree()
    assert tree["stray"]["orphan"] is True
    assert "orphan" not in tree["main.work"]
    text = export.report()
    assert "[orphan thread]" in text
    # the schema tolerates the flag (snapshot stays exporter-valid)
    assert export.schema_problems(export.snapshot()) == []


# ---------------------------------------------------------------------------
# thread model (the registry.py contract)
# ---------------------------------------------------------------------------

def test_counter_hammer_two_threads():
    """The zero-lost-increment contract documented in obs/registry.py:
    bound-series ``add()`` is a single eval run on this interpreter, so
    two threads hammering one series under a 1µs switch interval lose
    nothing."""
    import sys
    import threading
    series = registry.counter("t.hammer").labels()
    n = 200_000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def _work():
            add = series.add
            for _ in range(n):
                add()

        threads = [threading.Thread(target=_work) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert series.n == 2 * n


def test_histogram_concurrent_observe_consistent():
    """Histogram ``observe`` takes the per-series lock (multi-field
    update): concurrent observers lose no events and the bucket counts
    sum to the total."""
    import sys
    import threading
    h = registry.histogram("t.hammer.hist", buckets=(0.5,)).labels()
    n = 50_000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def _work(v):
            for _ in range(n):
                h.observe(v)

        threads = [threading.Thread(target=_work, args=(v,))
                   for v in (0.1, 0.9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    val = h._value()
    assert val["count"] == 2 * n
    assert val["buckets"] == {"0.5": n, "+Inf": n}
    assert val["min"] == 0.1 and val["max"] == 0.9


# ---------------------------------------------------------------------------
# flight recorder (obs.flight)
# ---------------------------------------------------------------------------

@pytest.fixture()
def _flight():
    from consensus_specs_tpu.obs import flight
    flight.reset(refresh_env=True)
    flight.enable(True)
    yield flight
    flight.reset(refresh_env=True)


def test_flight_ring_wraparound(_flight, monkeypatch):
    monkeypatch.setenv("CS_TPU_FLIGHT_SIZE", "8")
    _flight.reset(refresh_env=True)
    _flight.enable(True)        # the off-leg (CS_TPU_FLIGHT=0) pins
    #                             the env default off; force-arm
    for i in range(20):
        _flight.record("note", f"n{i}")
    d = _flight.dump(trigger="manual")
    recs = d["threads"]["MainThread"]
    # the ring keeps exactly the LAST size records, in sequence order
    assert len(recs) == 8
    assert d["dropped"] == 12
    assert [r[3] for r in recs] == [f"n{i}" for i in range(12, 20)]
    seqs = [r[0] for r in recs]
    assert seqs == sorted(seqs)


def test_flight_disabled_records_nothing(_flight):
    _flight.enable(False)
    _flight.record("note", "dropped-on-floor")
    assert _flight.record_count() == 0
    d = _flight.dump(trigger="manual")
    assert d["enabled"] is False
    assert d["threads"] == {}


def test_flight_dump_counters_and_format(_flight):
    with counting() as delta:
        _flight.record("note", "hello", 1.5)
        d = _flight.dump(trigger="manual")
    assert delta["obs.flight.records"] == 1
    assert delta["obs.flight.dumps{trigger=manual}"] == 1
    text = _flight.format_dump(d)
    assert "hello" in text and "MainThread" in text


def test_flight_spans_recorded_and_chrome_export(_flight, tmp_path):
    tracing.enable(True, counters=False)
    with tracing.span("t.flight.outer"):
        with tracing.span("t.flight.inner"):
            pass
    d = _flight.dump(trigger="manual")
    codes = [(r[2], r[3]) for r in d["threads"]["MainThread"]]
    assert ("span>", "t.flight.outer") in codes
    assert ("span<", "t.flight.inner") in codes
    # enters before exits, outer brackets inner
    assert codes.index(("span>", "t.flight.outer")) \
        < codes.index(("span>", "t.flight.inner")) \
        < codes.index(("span<", "t.flight.inner")) \
        < codes.index(("span<", "t.flight.outer"))
    out = tmp_path / "trace.json"
    _flight.write_chrome_trace(str(out), d)
    trace = json.loads(out.read_text())
    events = trace["traceEvents"]
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"t.flight.outer", "t.flight.inner"} <= names
    assert any(e["ph"] == "M" for e in events)


def test_flight_cross_thread_dump_merged_by_thread(_flight):
    import threading

    def _work():
        _flight.record("note", "from-worker")

    t = threading.Thread(target=_work, name="t-flight-worker")
    t.start()
    t.join()
    _flight.record("note", "from-main")
    d = _flight.dump(trigger="manual")
    assert [r[3] for r in d["threads"]["t-flight-worker"]] \
        == ["from-worker"]
    assert "from-main" in [r[3] for r in d["threads"]["MainThread"]]


# ---------------------------------------------------------------------------
# live telemetry plane (obs.serve)
# ---------------------------------------------------------------------------

def _http_get(url):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


def test_http_plane_endpoints_and_health_flip():
    from consensus_specs_tpu import supervisor
    registry.counter("t.http.seen").labels().add(3)
    supervisor.reset()
    try:
        with obs.serve(0) as srv:
            code, body = _http_get(srv.url + "/metrics")
            assert code == 200
            assert b"cs_tpu_t_http_seen" in body
            code, body = _http_get(srv.url + "/healthz")
            assert code == 200 and json.loads(body)["ok"] is True
            code, body = _http_get(srv.url + "/snapshot")
            assert code == 200
            snap = json.loads(body)
            assert export.schema_problems(snap) == []
            code, _ = _http_get(srv.url + "/nope")
            assert code == 404
            # forced quarantine flips /healthz non-200, naming the site
            with supervisor.quarantine_hook(lambda s, d: None):
                supervisor.quarantine("t.http.site", "forced by test")
            code, body = _http_get(srv.url + "/healthz")
            health = json.loads(body)
            assert code == 503 and health["ok"] is False
            assert "t.http.site" in health["quarantined"]
            supervisor.reset()
            code, _ = _http_get(srv.url + "/healthz")
            assert code == 200
        assert registry.counter("obs.http.requests").total() >= 6
    finally:
        supervisor.reset()
