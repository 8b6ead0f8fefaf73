"""Record ``data/span_probe.xplane.pb``, the trace ``test_span_reduce.py``
reads, on one accelerator, with the profiler options of the benchmark's
traced run:

    python3 perfbench/tests/record_span_probe.py [out_dir]

Inside one ``bench.flush`` annotation, with ``obs.tracing`` spans on:

- ``probe.outer``: ``f`` twice, and inside ``probe.inner`` ``g`` on a
  (256, 256) input;
- a 20 ms sleep outside any span;
- ``g`` on a (1024, 1024) input outside any span.

Every launch waits for its result, so the device idles between them.
"""
import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

SLEEP_S = 0.02


def main(out_dir=os.path.join(HERE, "data")):
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu.obs import tracing

    @jax.jit
    def f(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    @jax.jit
    def g(x):
        return jnp.sin(x @ x.T) @ x

    small = jnp.ones((256, 256), jnp.float32) / 256
    large = jnp.ones((1024, 1024), jnp.float32) / 1024
    for x in (small, large):                  # compile outside the trace
        f(x).block_until_ready()
        g(x).block_until_ready()
    tmp = os.path.join(out_dir, ".span_probe")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    tracing.enable(True)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    time.sleep(0.05)                          # the device tracer starts
    with jax.profiler.TraceAnnotation("bench.flush"):
        with tracing.span("probe.outer"):
            y = f(small).block_until_ready()
            with tracing.span("probe.inner"):
                g(y).block_until_ready()
            f(y).block_until_ready()
        time.sleep(SLEEP_S)
        g(large).block_until_ready()
    jax.profiler.stop_trace()
    tracing.enable(False)
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    out = os.path.join(out_dir, "span_probe.xplane.pb")
    shutil.copyfile(path, out)
    shutil.rmtree(tmp)
    print(f"{out}: {os.path.getsize(out)} bytes on "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(*sys.argv[1:])
