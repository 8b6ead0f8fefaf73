"""Prewarm the persistent XLA compile cache for this machine.

Compiles the staged programs that ``bench.py``, the RLC flush,
``__graft_entry__`` and (with two or more devices) its multichip dryrun
dispatch, so later processes replay executables from the cache
(``utils/jax_env.cache_dir``: ``$JAX_COMPILATION_CACHE_DIR`` or
``.jax_cache/``) instead of paying the cold compile.  Everything runs
in this one process, on whatever platform JAX reports.

Run ``make warm`` (or ``python -m consensus_specs_tpu.tools.warm``)
after checkout / dependency changes.  Stages are warmed in increasing
cost order and each prints its wall time.
"""
import os
import sys
import time


def _log(msg):
    print(f"[warm {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def warm_bench(batch=None):
    """Compile the batched FastAggregateVerify pipeline bench.py measures."""
    from consensus_specs_tpu.ops import bls_jax
    from consensus_specs_tpu.tools import bench_fixtures

    pks, msg, agg = bench_fixtures.load()
    b = batch or bls_jax.bucket_b()
    t0 = time.time()
    out = bls_jax.verify_aggregates_batch([(pks, msg, agg)] * b)
    assert all(out)
    _log(f"bench pipeline (batch {b}, 64 keys): {time.time() - t0:.1f}s")


def warm_rlc():
    """Compile the RLC flush pipeline (``ops/bls_rlc`` jax path): the
    per-item aggregate + 128-bit scale, the signature G2 MSM, and the
    flat-pairs product pairing with its log-depth f12 fold — the
    programs ``DeferredBatch.flush`` dispatches under the jax backend.

    Shapes compile per item-count bucket (lane_bucket of n items; the
    flat pairs axis buckets at the next power of two above n+1), so by
    default this warms the smallest bucket only; set
    ``CS_TPU_WARM_RLC_ITEMS`` to the expected block size (e.g. 130 for
    a full 128-attestation block) to also pre-pay that bucket's
    compiles — multi-minute on XLA:CPU, worth it before throughput runs.
    """
    from consensus_specs_tpu.ops import bls_rlc
    from consensus_specs_tpu.tools import bench_fixtures

    pks, msg, agg = bench_fixtures.load()
    n_items = max(1, int(os.environ.get("CS_TPU_WARM_RLC_ITEMS", "1")))
    items = [(pks, msg, agg)] * n_items
    t0 = time.time()
    verdict = bls_rlc.combined_check(items, [], "jax")
    assert verdict is True
    _log(f"rlc combined check ({n_items} item(s), 64 keys): "
         f"{time.time() - t0:.1f}s")


def warm_fft(n: int = None, rows: int = None):
    """Compile the ``fr_fft`` limb kernel at the DAS shape: the batched
    (B, 8192, 16) butterflies the ``CS_TPU_DAS_FFT=limb`` erasure-
    recovery path dispatches (``das/kernels._fft_rows``).  Forward AND
    inverse domains compile separately (distinct twiddle tables), so
    both are warmed — multi-minute cold on XLA:CPU, which is exactly
    why this runs here and not in the first on-device benchmark.
    ``CS_TPU_WARM_FFT_ROWS`` widens the batch to the expected
    concurrent-blob count (default 1 row warms the shape bucket)."""
    from consensus_specs_tpu.ops import kzg as K
    from consensus_specs_tpu.ops.jax_bls import fr_fft
    from consensus_specs_tpu.utils import env_flags

    ext = n or 2 * 4096          # FIELD_ELEMENTS_PER_BLOB extension
    b = rows or max(1, int(env_flags.knob("CS_TPU_WARM_FFT_ROWS", "1")))
    roots = list(K.compute_roots_of_unity(ext))
    data = [[(i * 1103515245 + j) % K.BLS_MODULUS for j in range(ext)]
            for i in range(b)]
    t0 = time.time()
    fwd = fr_fft.fft_batch(data, roots)
    back = fr_fft.fft_batch(fwd, roots, inv=True)
    assert back == data, "fft roundtrip mismatch"
    _log(f"fr_fft limb kernel ({b}x{ext}, fwd+inv roundtrip): "
         f"{time.time() - t0:.1f}s")


def warm_entry():
    """Compile the single-chip graft-entry program (the flagship pairing
    check the driver compile-checks)."""
    import importlib
    import jax
    import numpy as _np
    g = importlib.import_module("__graft_entry__")
    t0 = time.time()
    fn, args = g.entry()
    assert bool(_np.asarray(jax.jit(fn)(*args)).all())
    _log(f"graft entry compile check: {time.time() - t0:.1f}s")


def warm_dryrun():
    """Compile the sharded verification step over every device JAX
    reports (skipped on a single device: there is no mesh to warm)."""
    import importlib
    import jax
    n = len(jax.devices())
    if n < 2:
        _log("dryrun skipped: one device")
        return
    t0 = time.time()
    importlib.import_module("__graft_entry__").dryrun_multichip(n)
    _log(f"dryrun_multichip({n}): {time.time() - t0:.1f}s")


def main():
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--stage",
                        choices=("all", "bench", "dryrun", "entry", "rlc",
                                 "fft"),
                        default="all")
    ns = parser.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from consensus_specs_tpu.utils.jax_env import setup_compile_cache
    cache = setup_compile_cache()
    import jax
    _log(f"cache dir: {cache}; platform: {jax.default_backend()}")
    if ns.stage in ("all", "bench"):
        warm_bench()
    if ns.stage in ("all", "rlc"):
        warm_rlc()
    if ns.stage in ("all", "fft"):
        warm_fft()
    if ns.stage in ("all", "entry"):
        warm_entry()
    # last: it shares most staged programs with the bench pipeline
    if ns.stage in ("all", "dryrun"):
        warm_dryrun()
    _log("done")


if __name__ == "__main__":
    main()
