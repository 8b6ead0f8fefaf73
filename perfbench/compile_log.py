"""Compile accounting from JAX's own ``jax.monitoring`` duration events.

Copied from ``chip_smoke.CompileLog`` (PR 21) so that later PRs cannot
move it.  Backend compilations (an XLA compile, or a load from the
persistent cache) are kept per program name with their seconds; the
host's jaxpr tracing and MLIR lowering, which the persistent cache does
not keep, are summed apart.  ``mark``/``since`` split set-up from the
measured window.
"""
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration")


class CompileLog:
    def __init__(self):
        self.events = []            # (program name, backend compile s)
        self.lower = []             # trace + lower seconds, one per event

    def __call__(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE:
            self.events.append((kwargs.get("fun_name", "?"), duration))
        elif event in TRACE_LOWER:
            self.lower.append(duration)

    def mark(self):
        return len(self.events), len(self.lower)

    def since(self, mark=(0, 0)):
        ev = self.events[mark[0]:]
        per = {}
        for name, s in ev:
            n, t = per.get(name, (0, 0.0))
            per[name] = (n + 1, t + s)
        return {"programs": len(ev),
                "compile_s": sum(s for _, s in ev),
                "trace_lower_s": sum(self.lower[mark[1]:]),
                "per_program": {k: {"n": n, "s": t}
                                for k, (n, t) in sorted(per.items())}}


def install():
    """Register a fresh log with ``jax.monitoring`` and return it."""
    import jax
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    return log
