"""setup.compile_s: seconds of XLA backend compilation during set-up, or of
loading the programs from the persistent compile cache, from JAX's own
``jax.monitoring`` duration events."""


def read(run):
    return run.compile_setup["compile_s"]
