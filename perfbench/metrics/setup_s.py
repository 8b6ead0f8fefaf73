"""setup_s: seconds from process start to the window's opening: input
generation, loading, tracing and lowering, compiling or loading from the
persistent cache, and the warm-up operation (host clock)."""


def read(run):
    return run.setup_s
