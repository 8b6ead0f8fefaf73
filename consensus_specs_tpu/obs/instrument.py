"""From-outside span instrumentation of the spec classes.

The fork ladders keep their method bodies spec-shaped (hand-written
classes mirror the markdown; compiled classes ARE the markdown), so
tracing wraps them from outside, last of the installs
``forks.install_engines`` applies to every fork class of both ladders
(and to ``ForkChoiceMixin``), so a span encloses the engine dispatch.

Only methods defined on the class itself are wrapped (an inherited
method is already wrapped on the base; a fork's override gets its own
wrapper), and wrapping is idempotent.  The wrapper's disabled path is
one module-global read on top of the original call — per-slot / per-
block granularity, so it never sits inside a per-validator loop.

These wrappers record on whichever thread calls them: spec code runs
on the main thread by contract (``serving/pipeline.py`` keeps the
worker lane to pure verification), so wrapped spans nest under the
caller's open span there — e.g. ``on_block`` under ``serving.window``.
Code that DOES move work to a thread must hand over a
``tracing.capture_context()`` / ``adopt_context()`` pair, or its spans
root an ``[orphan thread]`` subtree (speclint O504 flags the miss).
"""
import functools

from . import tracing

# The traced spec surface, method -> span name: block/epoch-granularity
# transition stages (``st.*``), the block operations whose cost scales
# with the registry, and the fork-choice handlers.  Order is
# irrelevant; nesting comes from runtime call structure.
TRACED_METHODS = {
    "state_transition": "st.state_transition",
    "process_slots": "st.process_slots",
    "process_slot": "st.process_slot",
    "process_epoch": "st.process_epoch",
    "process_block": "st.process_block",
    "process_operations": "st.process_operations",
    "process_attestation": "st.block.attestations",
    "process_sync_aggregate": "st.block.sync_aggregate",
    "process_withdrawals": "st.block.withdrawals",
    "on_block": "on_block",
    "on_attestation": "on_attestation",
    "on_tick": "on_tick",
}


def install_tracing(cls) -> None:
    """Wrap ``cls``'s own transition-stage methods in tracing spans."""
    for name, span_name in TRACED_METHODS.items():
        fn = cls.__dict__.get(name)
        if fn is None or getattr(fn, "_obs_span_wrapper", False):
            continue
        setattr(cls, name, _make_wrapper(span_name, fn))


def _make_wrapper(name, fn):
    _span = tracing.span

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        if not tracing._enabled:
            return fn(self, *args, **kwargs)
        with _span(name):
            return fn(self, *args, **kwargs)

    wrapper._obs_span_wrapper = True
    return wrapper
