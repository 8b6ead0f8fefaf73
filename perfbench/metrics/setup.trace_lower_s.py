"""setup.trace_lower_s: seconds of jaxpr tracing and MLIR lowering during
set-up, from JAX's own ``jax.monitoring`` duration events.  The
persistent compile cache does not keep this work, so every run pays it."""


def read(run):
    return run.compile_setup["trace_lower_s"]
