"""The compile-log reduction on synthetic ``jax.monitoring`` events."""
import compile_log as CL


def feed(log, events):
    for event, duration, kw in events:
        log(event, duration, **kw)


def test_split_setup_from_window():
    log = CL.CompileLog()
    feed(log, [
        (CL.TRACE_LOWER[0], 0.5, {}),
        (CL.TRACE_LOWER[1], 0.25, {}),
        (CL.BACKEND_COMPILE, 2.0, {"fun_name": "_j_tree_sum"}),
        (CL.BACKEND_COMPILE, 1.0, {"fun_name": "_j_tree_sum"}),
        (CL.BACKEND_COMPILE, 3.0, {"fun_name": "_j_f12_mul"}),
        ("/jax/some/other/event", 9.0, {}),
    ])
    mark = log.mark()
    setup = log.since((0, 0))
    assert setup["programs"] == 3
    assert setup["compile_s"] == 6.0
    assert setup["trace_lower_s"] == 0.75
    assert setup["per_program"] == {"_j_f12_mul": {"n": 1, "s": 3.0},
                                    "_j_tree_sum": {"n": 2, "s": 3.0}}
    assert log.since(mark) == {"programs": 0, "compile_s": 0,
                               "trace_lower_s": 0, "per_program": {}}
    feed(log, [(CL.BACKEND_COMPILE, 4.0, {}), (CL.TRACE_LOWER[0], 1.0, {})])
    window = log.since(mark)
    assert window["programs"] == 1 and window["per_program"] == {
        "?": {"n": 1, "s": 4.0}}
    assert window["trace_lower_s"] == 1.0


def test_install_listens_to_jax():
    import jax
    import jax.numpy as jnp
    log = CL.install()
    mark = log.mark()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    got = log.since(mark)
    assert got["programs"] >= 1 and got["trace_lower_s"] > 0
