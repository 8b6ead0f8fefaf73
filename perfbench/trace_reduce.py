"""Reduce a profiler trace (``.xplane.pb``) to device busy time,
per-program device time and idle gaps named by what the host was doing.

Reads the trace with ``jax.profiler.ProfileData`` alone.  Device planes
are the ``/device:TPU:<n>`` (or ``GPU``) planes; an operation's interval
is an event of the plane's ``XLA Ops`` line, a program's the events of
its ``XLA Modules`` line.  The benchmark brackets each window operation
in a ``jax.profiler.TraceAnnotation`` whose name starts with a fixed
prefix; the traced window runs from the first such annotation's start
to the last one's end, on the trace's own clock.

The device clock of a TPU trace runs apart from the host's by about a
millisecond or two.  Every program run on the device has a correlation
id (stat ``_c``) that the host's ``CompleteCallbacks`` event for it
repeats; device times are moved onto the host clock by the median gap
between a program's end and the start of its completion callback.

- busy: the union of operation intervals inside the window, per device,
  averaged over devices;
- per program: the summed durations of its module events inside the
  window, averaged over devices, under its name without the trailing
  ``(<id>)``;
- idle gaps: the complement of the busy union inside the window (first
  device), each named by the innermost host event that covers its
  midpoint (a benchmark annotation, or a runtime event JAX records).
"""
import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals):
    return sum(e - s for s, e in intervals)


def program_name(name):
    return _ID_SUFFIX.sub("", name)


_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def is_device_plane(name):
    return bool(_DEVICE_PLANE.match(name))


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _correlated(events, name=None):
    """``{correlation id: (start, end)}`` of events (named ``name``)."""
    out = {}
    for ev in events:
        if name is None or ev.name == name:
            c = dict(ev.stats).get("_c")
            if c is not None:
                out[c] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return out


def clock_offset(pd):
    """Nanoseconds to add to device times to put them on the host clock."""
    done, ran = {}, {}
    for plane in pd.planes:
        for line in plane.lines:
            if is_device_plane(plane.name) and line.name == MODULES_LINE:
                ran.update(_correlated(line.events))
            elif plane.name.startswith("/host:CPU"):
                done.update(_correlated(line.events, "CompleteCallbacks"))
    gaps = sorted(done[c][0] - ran[c][1] for c in ran if c in done)
    return gaps[len(gaps) // 2] if gaps else 0


def planes(pd, prefix):
    """``(devices, host)``: per device plane ``{line name: events}`` on
    the host clock, and the events of the host line that holds the
    benchmark's annotations, as one sorted ``(starts, events)`` list per
    nesting depth."""
    devices, host = [], None
    offset = clock_offset(pd)
    for plane in pd.planes:
        if is_device_plane(plane.name):
            devices.append({line.name: [(n, s + offset, e + offset)
                                        for n, s, e in _events(line)]
                            for line in plane.lines})
        elif plane.name.startswith("/host:CPU") and host is None:
            for line in plane.lines:
                evs = _events(line)
                if any(n.startswith(prefix) for n, _, _ in evs):
                    host = _by_depth(evs)
                    break
    return devices, host or []


def _by_depth(events):
    levels, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1] <= s:
            stack.pop()
        while len(levels) <= len(stack):
            levels.append([])
        levels[len(stack)].append((s, e, name))
        stack.append(e)
    return [([s for s, _, _ in lv], lv) for lv in levels]


def innermost(host, t):
    """Name of the deepest host event covering time ``t``."""
    name = None
    for starts, evs in host:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and evs[i][1] > t:
            name = evs[i][2]
    return name or "(no host event)"


def reduce(pd, prefix="bench."):
    """The reduction of one ``ProfileData``; None when the trace holds
    no benchmark annotation or no device plane."""
    devices, host = planes(pd, prefix)
    annots = sorted((s, e, n) for _, evs in host for s, e, n in evs
                    if n.startswith(prefix))
    if not annots or not devices:
        return None
    lo, hi = annots[0][0], max(e for _, e, _ in annots)
    busy_per_dev, programs = [], {}
    for dev in devices:
        ops = dev.get(OPS_LINE) or dev.get(MODULES_LINE) or []
        busy_per_dev.append(merge(clip([(s, e) for _, s, e in ops], lo, hi)))
        for name, s, e in dev.get(MODULES_LINE, []):
            for cs, ce in clip([(s, e)], lo, hi):
                key = program_name(name)
                programs[key] = programs.get(key, 0) + (ce - cs) / len(devices)
    busy = busy_per_dev[0]
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = [(innermost(host, (s + e) / 2), (e - s) / 1e9) for s, e in gaps]
    per_annotation = [(n, (e - s) / 1e9, total(clip(busy, s, e)) / 1e9)
                      for s, e, n in annots]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(total(b) for b in busy_per_dev) / len(busy_per_dev) / 1e9,
        "per_program_s": {k: v / 1e9 for k, v in programs.items()},
        "per_annotation": per_annotation,
        "top_programs": [[k, v / 1e9] for k, v in
                         sorted(programs.items(), key=lambda kv: -kv[1])[:10]],
        "top_gaps": [[n, s] for n, s in sorted(named, key=lambda x: -x[1])[:10]],
    }


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def reduce_file(path, prefix="bench."):
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), prefix)


def reduce_dir(trace_dir, prefix="bench."):
    path = find_xplane(trace_dir)
    return reduce_file(path, prefix) if path else None
