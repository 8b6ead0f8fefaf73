"""span_reduce and the metrics that read the program's own spans.

``data/span_probe.xplane.pb`` was recorded on one TPU v5e by
``record_span_probe.py``: inside one ``bench.flush`` annotation, ``f``
twice under ``probe.outer``, ``g`` on a small input under
``probe.outer/probe.inner``, a 20 ms sleep outside any span, then ``g``
on a large input outside any span.  ``data/chip_probe.xplane.pb``
(``test_trace_reduce.py``) holds no program span, as a trace of a
program without them does."""
import os
import shutil

import pytest

import run as R
import span_reduce as S
import tiny
import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROBE = os.path.join(DATA, "span_probe.xplane.pb")
NO_SPANS = os.path.join(DATA, "chip_probe.xplane.pb")
NAMES = frozenset({"probe.outer", "probe.inner"})
SPAN_METRICS = ("device_idle_unattributed.blocksig",
                "bls_msm_device_ms.blocksig")


def metric(name):
    return R.load_module(os.path.join(tiny.PERFBENCH, "metrics", name + ".py"),
                         "test_metric_" + name.replace(".", "_"))


def profile(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


@pytest.fixture(scope="module")
def probe():
    return profile(PROBE)


def module_runs(pd):
    """``[(program, duration ns)]`` of the device's modules by run_id."""
    runs = []
    for plane in pd.planes:
        if T.is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == T.MODULES_LINE:
                    runs += [(dict(ev.stats)["run_id"],
                              T.program_name(ev.name), ev.duration_ns)
                             for ev in line.events]
    return [(p, d) for _, p, d in sorted(runs)]


def test_launches_count_the_outer_of_each_nested_pair(probe):
    _, host = T.planes(probe, "bench.")
    events = [ev for _, evs in host for ev in evs]
    raw = [n for _, _, n in events if n.startswith("PjitFunction(")]
    found = S.launches(events)
    assert {fn: len(v) for fn, v in found.items()} == {"f": 2, "g": 2}
    assert len(raw) == 8                    # each launch recorded twice
    runs = module_runs(probe)
    assert [p for p, _ in runs] == ["jit_f", "jit_g", "jit_f", "jit_g"]


def test_device_time_goes_to_the_span_that_launched_it(probe):
    r = S.reduce(probe, NAMES)
    (_, f1), (_, g_small), (_, f2), (_, g_large) = module_runs(probe)
    assert g_large > 10 * g_small           # the run_id order holds
    assert r["device_s"] == pytest.approx({
        "probe.outer": (f1 + f2) / 1e9,
        "probe.outer/probe.inner": g_small / 1e9,
        S.NO_SPAN: g_large / 1e9,
    }, rel=1e-9)
    assert r["span_events"] == 2 and r["annotations"] == 1


def test_the_sleep_outside_spans_is_unattributed_idle(probe):
    r = S.reduce(probe, NAMES)
    assert 0.02 <= r["unattributed_idle_s"] < 0.025
    # the device also idles inside the spans, between launches
    by_span = r["idle_by_span"]
    assert set(by_span) == {"probe.outer", "probe.outer/probe.inner",
                            S.NO_SPAN}
    assert by_span[S.NO_SPAN] == r["unattributed_idle_s"]
    assert sum(by_span.values()) == pytest.approx(r["idle_s"], rel=1e-9)
    whole = T.reduce(probe)
    assert r["idle_s"] == pytest.approx(
        whole["window_s"] - whole["busy_s"], rel=1e-6)


def test_without_program_spans_everything_is_unattributed():
    r = S.reduce(profile(NO_SPANS), NAMES)
    assert r["span_events"] == 0
    assert r["unattributed_idle_s"] == pytest.approx(r["idle_s"])
    assert set(r["device_s"]) == {S.NO_SPAN}


class FakeOp:
    def __init__(self, spans):
        self.spans = spans


def traced_run(trace_file, tmp_path, monkeypatch, names):
    shutil.copyfile(trace_file, tmp_path / "t.xplane.pb")
    monkeypatch.setattr(S, "TRACE_DIR", str(tmp_path))
    spans = {"bench.x/" + n: 0.1 for n in names}
    return R.Run(trace={"window_s": 1.0}, ops=[FakeOp(spans)])


def test_span_metrics_read_the_probe(tmp_path, monkeypatch):
    run = traced_run(PROBE, tmp_path, monkeypatch,
                     ["probe.outer", "probe.inner", "bls.stage.msm"])
    idle = metric("device_idle_unattributed.blocksig").read(run)
    r = S.reduce(profile(PROBE), S.span_names(run.ops))
    assert idle == pytest.approx(
        100 * r["unattributed_idle_s"] / r["idle_s"])
    assert 0 < idle < 100
    # no launch of the probe ran inside bls.stage.msm
    assert metric("bls_msm_device_ms.blocksig").read(run) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metrics_are_silent_without_program_spans(
        name, tmp_path, monkeypatch):
    run = traced_run(NO_SPANS, tmp_path, monkeypatch,
                     ["bls.rlc.combine", "bls.stage.msm"])
    assert metric(name).read(run) is None
    run.trace = None                        # an untraced run
    assert metric(name).read(run) is None


@pytest.fixture(scope="module")
def traced_window():
    """A tiny window of RLC flushes on the numpy kernels with spans on,
    the pk_rows counter zeroed first, as a traced run reads it."""
    from consensus_specs_tpu.obs import registry, tracing
    from consensus_specs_tpu.utils import bls
    D = tiny.driver("sigflush")
    cfg = tiny.config("blocksig-mainnet-1m", **tiny.TINY_SIGFLUSH)
    work = D.build(cfg, tiny.mix("blocksig.full", pool_flushes=2), 2 ** 31 + 7)
    with tiny.patched(bls, "use_fastest", bls.use_jax):
        registry.reset("bls.rlc.pk_rows")
        D.warm(work)
        tracing.enable(True)
        try:
            _, ops = D.window(work, 0.0, tiny.NoTrace(), R.span_totals)
        finally:
            tracing.enable(False)
            tracing.reset()
    return R.Run(trace=None, ops=ops)


def test_sig_decompress_reads_its_span_under_the_combine(traced_window):
    ms = metric("bls_sig_decompress_ms.blocksig").read(traced_window)
    host = metric("bls_host_ms.blocksig").read(traced_window)
    assert 0 < ms < host


def test_row_fill_is_live_over_dispatched_rows(traced_window):
    # 3 aggregates of 4 keys, a sync aggregate of 4 and 2 singles: 18
    # live rows of 6 lanes x 8 (the padded width's floor) dispatched
    fill = metric("bls_row_fill.blocksig").read(traced_window)
    assert fill == pytest.approx(100 * 18 / 48)
