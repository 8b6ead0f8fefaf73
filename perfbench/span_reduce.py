"""Reduce a traced run's profiler trace by the program's own spans: the
device time of the programs each stage launched, and the device idle
time that no stage accounts for.

An enabled ``obs.tracing`` span is also a profiler TraceMe on the host
line of the thread that opened it, the line that holds the benchmark's
``bench.`` annotations (``trace_reduce.planes``).  A program span is an
event of that line whose name is a name of the run's own span tree (the
``op.spans`` keys).

Attribution of a launch: on that host line every launch of a jitted
function ``<fn>`` is a ``PjitFunction(<fn>)`` event (a TPU v5e records
it as a nested pair of such events; only the outer one counts), and on
a device plane's ``XLA Modules`` line every run is a
``jit_<fn>(<fingerprint>)`` event whose ``run_id`` grows per launch.
The k-th launch of ``<fn>`` is matched to the k-th ``jit_<fn>`` module
in ``run_id`` order, and the module's device time is credited to the
program spans open at the launch's start, as the path of their names
from the outermost (``a/b/c``, as ``op.spans`` keys it).  Modules whose
launch is not in the trace go under ``(no span)``.
"""
import bisect
import functools
import os
import re

import trace_reduce as T

TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".trace")
NO_SPAN = "(no span)"
_LAUNCH = re.compile(r"^PjitFunction\((.+)\)$")


def span_names(ops):
    """Every name in the run's span tree (``op.spans`` keys are paths)."""
    return frozenset(n for op in ops for path in op.spans
                     for n in path.split("/"))


def launches(events):
    """``{fn: [start, ...]}`` of the outer ``PjitFunction(<fn>)`` events
    among ``(start, end, name)`` host events, in time order."""
    out, last = {}, {}
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        m = _LAUNCH.match(name)
        if not m:
            continue
        fn = m.group(1)
        prev = last.get(fn)
        if prev is not None and prev[0] <= s and e <= prev[1]:
            continue                        # the inner event of a pair
        last[fn] = (s, e)
        out.setdefault(fn, []).append(s)
    return out


def modules(pd):
    """Per device plane, ``{program: [duration ns, ...]}`` of its
    ``XLA Modules`` events in ``run_id`` order."""
    out = []
    for plane in pd.planes:
        if not T.is_device_plane(plane.name):
            continue
        runs = {}
        for line in plane.lines:
            if line.name != T.MODULES_LINE:
                continue
            for ev in line.events:
                run_id = dict(ev.stats).get("run_id", 0)
                runs.setdefault(T.program_name(ev.name), []).append(
                    (run_id, ev.start_ns, ev.duration_ns))
        out.append({k: [d for _, _, d in sorted(v)] for k, v in runs.items()})
    return out


def open_spans(host, names, t):
    """Path of the program spans open at ``t`` on the by-depth host line."""
    path = []
    for starts, evs in host:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and evs[i][1] > t and evs[i][2] in names:
            path.append(evs[i][2])
    return "/".join(path) or NO_SPAN


def reduce(pd, names, prefix="bench."):
    """The reduction of one ``ProfileData``; None when the trace holds no
    benchmark annotation or no device plane.

    - ``device_s``: ``{span path: device seconds}``, averaged over devices;
    - ``idle_s``: device idle inside the benchmark window (first device);
    - ``idle_by_span``: ``{span path: seconds}`` of that idle time, by
      the program spans open on the host line meanwhile;
    - ``unattributed_idle_s``: the part of it during which no program
      span is open (``idle_by_span[NO_SPAN]``);
    - ``span_events``: program-span events on the host line (0 when the
      program writes no spans into the trace);
    - ``annotations``: the benchmark annotations (traced operations).
    """
    devices, host = T.planes(pd, prefix)
    events = [ev for _, evs in host for ev in evs]
    annots = [(s, e) for s, e, n in events if n.startswith(prefix)]
    if not annots or not devices:
        return None
    lo, hi = min(s for s, _ in annots), max(e for _, e in annots)
    spans = [(s, e) for s, e, n in events if n in names]
    ops = devices[0].get(T.OPS_LINE) or devices[0].get(T.MODULES_LINE) or []
    busy = T.merge(T.clip([(s, e) for _, s, e in ops], lo, hi))
    idle, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    cuts = sorted({t for span in spans for t in span if lo < t < hi})
    idle_by_span = {}
    for s, e in idle:
        edges = ([s] + cuts[bisect.bisect_right(cuts, s):
                            bisect.bisect_left(cuts, e)] + [e])
        for a, b in zip(edges, edges[1:]):
            path = open_spans(host, names, (a + b) / 2)
            idle_by_span[path] = idle_by_span.get(path, 0.0) + (b - a) / 1e9
    found = launches(events)
    runs = modules(pd)
    device_s = {}
    for per_program in runs:
        for program, durations in per_program.items():
            starts = found.get(program[len("jit_"):], []) \
                if program.startswith("jit_") else []
            for k, d in enumerate(durations):
                path = (open_spans(host, names, starts[k])
                        if k < len(starts) else NO_SPAN)
                device_s[path] = device_s.get(path, 0.0) + d / 1e9 / len(runs)
    return {
        "device_s": device_s,
        "idle_s": T.total(idle) / 1e9,
        "idle_by_span": idle_by_span,
        "unattributed_idle_s": idle_by_span.get(NO_SPAN, 0.0),
        "span_events": len(spans),
        "annotations": len(annots),
    }


@functools.lru_cache(maxsize=4)
def reduce_file(path, names):
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), names)


def reduce_run(run):
    """The reduction of a traced run's trace, or None for a run without
    one (``--trace 0``, or no trace written)."""
    if not getattr(run, "trace", None):
        return None
    path = T.find_xplane(TRACE_DIR)
    return reduce_file(path, span_names(run.ops)) if path else None
