"""Hierarchical tracing spans: a per-replay span tree with wall-clock,
call counts, self-vs-cumulative time, and attached counter deltas.

A span both feeds a flat per-name aggregate (``stats()``, with
explicit self-time so nested spans do not double-count) and a
position-aware tree (``span_tree()``) keyed by call path, so a 32-slot
replay reads as::

    state_transition            32   1.84s (self 0.02s)
      process_slots             32   1.21s (self 0.11s)
        process_epoch            4   0.63s ...
        hash_forest.flush      288   0.41s ...
          sha256.dispatch     1152   0.38s ...

Gating (registered in ``utils/env_flags.py``):

* ``CS_TPU_PROFILE=1`` — spans record timing (flat stats + tree).
* ``CS_TPU_TRACE=1``   — additionally attaches per-span counter deltas
  (a registry-wide counter diff on entry/exit; implies PROFILE).

Disabled path (the default, speclint O5xx's sanctioned pattern): one
module-global read in ``__enter__`` and one attribute test in
``__exit__`` — branch-predictable, allocation-free, and measured at
<2% on the 32-slot replay by ``benchmarks/bench_obs_overhead.py``.

An enabled span is also a profiler TraceMe of the same name
(``jax.profiler.TraceAnnotation``), so inside a profiler session
(``jax.profiler.start_trace``) spans land on the calling thread's host
line of the trace, nested as they ran, on the clock the device events
are aligned to.  Outside a session a TraceMe costs one atomic check.
``obs`` never imports jax: the annotation class is resolved once jax has
been imported by someone else.

Span state is thread-local, so by default concurrent threads build
disjoint subtrees under the shared root.  Cross-thread causality is
explicit: the submitting thread calls :func:`capture_context` while
its span of interest is open, hands the returned :class:`TraceContext`
to the worker, and the worker wraps its work in
:func:`adopt_context` — its spans then parent under the captured node
(one causally-linked tree per request) and carry the context's
``trace_id``.  A root-level subtree opened on a non-main thread that
*didn't* adopt a context is flagged ``orphan`` in :func:`span_tree`
so reports can call out unattributed worker-lane time instead of
silently merging it (speclint O504 statically flags thread submits
that skip the handoff).
"""
import itertools
import sys
import threading
import time

from ..utils import env_flags
from . import flight
from . import registry

_enabled = env_flags.PROFILE or env_flags.TRACE
_trace_counters = env_flags.TRACE
_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation_cls():
    """The profiler's ``TraceAnnotation`` once jax is in ``sys.modules``,
    else None (spans before that carry no profiler event)."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _Node:
    """One position in the span tree (aggregated across invocations of
    the same call path)."""

    __slots__ = ("name", "count", "total", "child_total", "max",
                 "children", "counters", "orphan")

    def __init__(self, name):
        self.name = name
        self.count = 0
        self.total = 0.0        # cumulative wall-clock
        self.child_total = 0.0  # time attributed to child spans
        self.max = 0.0
        self.children = {}      # name -> _Node
        self.counters = {}      # metric+labels -> cumulative delta
        self.orphan = False     # root created on a non-adopted thread


_root = _Node("<root>")
# flat per-name aggregate (the profiling.stats() surface):
# name -> [count, cum_total, max]; self-time is derived from the tree
# (per-position child_total) at stats() time, not stored here
_flat = {}
_tls = threading.local()


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = [_root]
        _tls.stack = st
    return st


def enable(on: bool = True, counters=None) -> None:
    """Turn span recording on/off at runtime (the env flags set the
    default).  ``counters`` optionally overrides counter-delta
    attachment; default: leave the CS_TPU_TRACE-derived setting."""
    global _enabled, _trace_counters
    _enabled = on
    if counters is not None:
        _trace_counters = counters


def is_enabled() -> bool:
    return _enabled


def trace_counters_enabled() -> bool:
    return _trace_counters


def reset() -> None:
    """Drop all recorded spans (flat stats and the tree) and re-seed
    the trace-id counter: a fresh tree hands out ids from 1 again, so
    seeded replays leave byte-deterministic flight tails."""
    global _trace_ids
    _flat.clear()
    _root.children.clear()
    _root.count = 0
    _root.total = _root.child_total = _root.max = 0.0
    _root.counters.clear()
    _trace_ids = itertools.count(1)


_trace_ids = itertools.count(1)


class TraceContext:
    """An explicit cross-thread handoff of one tree position.

    Captured on the thread whose span should become the parent, adopted
    (usually once) on the thread doing work on its behalf.  ``trace_id``
    is a process-unique request identifier the pipeline threads through
    window ingest, the flush-worker submit and the barrier join.
    Concurrent adoption from *different* threads is allowed — the
    serving barrier joins a window whose flush worker is still inside
    its adoption — but a thread re-adopting a context it already holds
    is refused (it would double-push the same node on one stack)."""

    __slots__ = ("node", "trace_id", "_threads")

    def __init__(self, node, trace_id):
        self.node = node
        self.trace_id = trace_id
        self._threads = set()   # idents currently inside adopt_context

    def __repr__(self):
        where = self.node.name if self.node is not None else None
        return f"TraceContext(trace_id={self.trace_id}, node={where!r})"


def capture_context():
    """Capture the calling thread's current tree position (the
    innermost open span) for adoption on another thread.  Returns
    ``None`` when spans are disabled — :func:`adopt_context` treats
    ``None`` as a no-op, so call sites need no gating of their own."""
    if not _enabled:
        return None
    return TraceContext(_stack()[-1], next(_trace_ids))


class adopt_context:
    """Context manager parenting the calling thread's spans under a
    captured :class:`TraceContext` — the worker half of the handoff.

    Exception-safe: unwinding pops everything the adopted region
    pushed, even if a span inside leaked (the stack is restored to its
    pre-adoption shape).  ``None`` (or a context captured while
    disabled) adopts nothing and costs one attribute test."""

    __slots__ = ("ctx", "_pushed")

    def __init__(self, ctx):
        self.ctx = ctx
        self._pushed = False

    def __enter__(self):
        ctx = self.ctx
        if ctx is None or ctx.node is None or not _enabled:
            return self
        ident = threading.get_ident()
        if ident in ctx._threads:
            raise RuntimeError(
                f"trace context {ctx.trace_id} already adopted on this "
                f"thread (double-adopt)")
        ctx._threads.add(ident)
        _stack().append(ctx.node)
        self._pushed = True
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._pushed:
            return False
        self._pushed = False
        self.ctx._threads.discard(threading.get_ident())
        stack = _stack()
        node = self.ctx.node
        # pop leaked spans (exception unwind) down to, and including,
        # the adopted node; never pop the thread's own root sentinel
        while len(stack) > 1 and stack[-1] is not node:
            stack.pop()
        if len(stack) > 1 and stack[-1] is node:
            stack.pop()
        return False


class span:
    """Context manager recording one span occurrence.

    Class-based (not a generator) so the disabled path is a plain
    attribute store + one global read, and instances are cheap enough
    to construct per call site.
    """

    __slots__ = ("name", "_node", "_t0", "_c0", "_annot")

    def __init__(self, name: str):
        self.name = name
        self._node = None

    def __enter__(self):
        if not _enabled:
            return self
        stack = _stack()
        parent = stack[-1]
        node = parent.children.get(self.name)
        if node is None:
            node = parent.children[self.name] = _Node(self.name)
            if (parent is _root and threading.current_thread()
                    is not threading.main_thread()):
                # a worker thread rooting its own subtree: no context
                # was adopted, so this time is causally unattributed
                node.orphan = True
        stack.append(node)
        self._node = node
        if flight._armed:
            flight.record("span>", self.name)
        self._c0 = registry.counter_values() if _trace_counters else None
        annot = _annotation or _annotation_cls()
        self._annot = (annot(self.name) if annot and annot.is_enabled()
                       else None)
        if self._annot is not None:
            self._annot.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        node = self._node
        if node is None:
            return False
        dt = time.perf_counter() - self._t0
        self._node = None
        if self._annot is not None:
            self._annot.__exit__(exc_type, exc, tb)
            self._annot = None
        if flight._armed:
            flight.record("span<", node.name, dt)
        stack = _stack()
        stack.pop()
        stack[-1].child_total += dt
        node.count += 1
        node.total += dt
        if dt > node.max:
            node.max = dt
        if self._c0 is not None:
            c1 = registry.counter_values()
            c0 = self._c0
            self._c0 = None
            for k, v in c1.items():
                d = v - c0.get(k, 0)
                if d:
                    node.counters[k] = node.counters.get(k, 0) + d
        f = _flat.get(node.name)
        if f is None:
            f = _flat[node.name] = [0, 0.0, 0.0]
        f[0] += 1
        f[1] += dt
        if dt > f[2]:
            f[2] = dt
        return False


def stats() -> dict:
    """Flat per-name aggregate:
    {name: {count, total_s, self_s, mean_s, max_s}}.

    ``total_s`` is cumulative (a nested span's time also counts in its
    parent); ``self_s`` excludes time spent inside child spans, so
    column sums of ``self_s`` are double-count-free.
    """
    # self-time lives on the tree (per-position child_total); fold it
    # into the flat view by name
    self_by_name = {}

    def _walk(node):
        for child in node.children.values():
            self_by_name[child.name] = (
                self_by_name.get(child.name, 0.0)
                + child.total - child.child_total)
            _walk(child)

    _walk(_root)
    out = {}
    for name, (c, total, mx) in _flat.items():
        self_s = self_by_name.get(name, total)
        out[name] = {"count": c, "total_s": round(total, 6),
                     "self_s": round(self_s, 6),
                     "mean_s": round(total / c, 6) if c else 0.0,
                     "max_s": round(mx, 6)}
    return out


def span_tree() -> dict:
    """Nested plain-data snapshot of the span tree:
    {name: {count, total_s, self_s, max_s, counters, children}}."""

    def _dump(node):
        out = {
            "count": node.count,
            "total_s": round(node.total, 6),
            "self_s": round(node.total - node.child_total, 6),
            "max_s": round(node.max, 6),
            "counters": dict(node.counters),
            "children": {n: _dump(c) for n, c in
                         sorted(node.children.items())},
        }
        if node.orphan:
            out["orphan"] = True
        return out

    return {n: _dump(c) for n, c in sorted(_root.children.items())}
