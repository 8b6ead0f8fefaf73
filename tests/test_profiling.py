"""Profiling-span registry tests (SURVEY §5 tracing/profiling role)."""
import time

from consensus_specs_tpu.obs import export, tracing


def test_spans_disabled_by_default_are_noop():
    tracing.enable(False)
    tracing.reset()
    with tracing.span("x"):
        pass
    assert tracing.stats() == {}


def test_spans_aggregate():
    tracing.enable(True)
    tracing.reset()
    try:
        for _ in range(3):
            with tracing.span("work"):
                time.sleep(0.01)
        st = tracing.stats()["work"]
        assert st["count"] == 3
        assert st["total_s"] >= 0.03
        assert st["max_s"] >= st["mean_s"] > 0
        assert "work" in export.report()
    finally:
        tracing.enable(False)
        tracing.reset()
