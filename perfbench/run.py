"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``); the mix names its driver
(``drivers/<driver>.py``: generator, window and output check); each
metric is read by ``metrics/<metric>.py``.  A later PR adds a cell by
adding such files and entries, and edits none.

One process holds the chip.  Set-up builds the inputs from ``--seed``
and warms the cell's own shapes; the window then runs for ``--seconds``
and closes at the end of the first operation to finish after that.
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics from spans, the compile log and
a profiler trace of a few window operations.  The numbers compared by
the output check are printed beside their limits, last on standard
error and under ``compared`` in the result, the last line of standard
output.  It exits nonzero without a result when JAX finds no
accelerator or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")


class Refused(Exception):
    """The run cannot produce a result (no chip, unknown cell)."""


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, cfg, mix


def load_driver(mix):
    return load_module(os.path.join(HERE, "drivers", mix["driver"] + ".py"),
                       "perfbench_driver_" + mix["driver"])


def metric_entries(bench, section, cell):
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def read_metrics(entries, run):
    out = {}
    for m in entries:
        reader = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                             "perfbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def setup_jax(chips):
    """The compile cache at a fixed path in the checkout, JAX's own
    compile events, and the devices; refuses without an accelerator."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")     # not /tmp/tpu_logs
    import compile_log
    clog = compile_log.install()
    from consensus_specs_tpu.utils.jax_env import setup_compile_cache
    setup_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise Refused("JAX found no accelerator (platform 'cpu')")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX reports "
                      f"{len(devices)}")
    return clog, devices[:chips]


class Run:
    """What metric readers see: the cell, set-up, window ops, compile
    log, spans per op and, with ``--trace 1``, the reduced trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def span_s(self):
        return self.ops[-1].t1 - self.t_open


def span_totals():
    """Cumulative seconds of every span position of the program's span
    tree, keyed by the path of names from the root (``a/b/c``)."""
    from consensus_specs_tpu.obs import tracing
    out = {}

    def walk(tree, prefix):
        for name, node in tree.items():
            out[prefix + name] = node["total_s"]
            walk(node["children"], prefix + name + "/")
    walk(tracing.span_tree(), "")
    return out


class Tracer:
    """A profiler trace of window op ``TRACED_OP`` alone, inside a
    ``TraceAnnotation``, without the programs' HLO protos.  One operation
    only: the device records every op of every loop iteration, and two
    flushes of the signature cell wrote 391 MiB, which ``stop_trace``
    took ~115 s to write (my chip run, PR 22).  Op 0 is left out: the
    device tracer takes a moment to start.  Without ``--trace 1`` it
    does nothing."""

    TRACED_OP = 1

    def __init__(self, enabled):
        self.dir = TRACE_DIR if enabled else None
        self.active = False

    @contextlib.contextmanager
    def around(self, i, name):
        if self.dir is None or i != self.TRACED_OP:
            yield
            return
        import shutil
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.active = True
        with jax.profiler.TraceAnnotation(name):
            yield
        self.close()

    def close(self):
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False


def spread(values):
    """min/median/max of the values that are there, for stderr."""
    v = sorted(x for x in values if x is not None)
    if not v:
        return "none"
    return f"min {v[0]:.3f} median {v[len(v) // 2]:.3f} max {v[-1]:.3f}"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(1, ROOT)
    try:
        bench, cell, cfg, mix = load_cell(args.workload)
        clog, devices = setup_jax(cell["chips"])
    except Refused as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    driver = load_driver(mix)
    work = driver.build(cfg, mix, args.seed)
    driver.warm(work)
    span_stats = None
    if args.trace:
        from consensus_specs_tpu.obs import tracing
        tracing.enable(True)
        span_stats = span_totals
    tracer = Tracer(args.trace)
    mark = clog.mark()
    compile_setup = clog.since((0, 0))
    setup_s = time.perf_counter() - T_START
    try:
        t_open, ops = driver.window(work, args.seconds, tracer, span_stats)
    finally:
        tracer.close()
    window_compiles = clog.since(mark)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devices)
    t0 = time.perf_counter()
    trace = None
    if tracer.dir:
        import trace_reduce
        trace = trace_reduce.reduce_dir(tracer.dir, prefix="bench.")
    t1 = time.perf_counter()
    compared = driver.check(work, ops)
    t2 = time.perf_counter()
    print(f"perfbench: set-up {setup_s:.3f}s, window {ops[-1].t1 - t_open:.3f}s"
          f" over {len(ops)} ops ({getattr(work, 'repeats', 0)} repeated; "
          f"op s {spread([op.t1 - op.t0 for op in ops])}; host s before the "
          f"device {spread([getattr(op, 'host_s', None) for op in ops])}); "
          f"backend compilations inside the window: "
          f"{window_compiles['programs']} {window_compiles['per_program']}; "
          f"trace reduced in {t1 - t0:.1f}s, output check {t2 - t1:.1f}s",
          file=sys.stderr, flush=True)
    if trace:
        print(f"perfbench: traced ops (name, wall s, device busy s) "
              f"{trace['per_annotation']}", file=sys.stderr, flush=True)
    correct = bool(compared) and all(
        limit is None or value <= limit for _, value, limit in compared)
    run = Run(cell=cell, cfg=cfg, mix=mix, seed=args.seed, setup_s=setup_s,
              t_open=t_open, ops=ops, compile_setup=compile_setup,
              trace=trace, work=work)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(metric_entries(bench, section, cell["name"]), run)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(ops),
              "failed": sum(1 for op in ops if op.failed),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["top_programs"],
                               "idle_gaps": trace["top_gaps"]}
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in compared}
    for name, value, limit in compared:
        print(f"compared {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
