"""Test-session config.

The session runs on the host CPU (``JAX_PLATFORMS=cpu``, the default
here unless the caller set it) as an 8-device virtual platform, pinned
before first import, so sharding/pjit tests exercise real multi-device
code paths without TPU hardware.  The chip itself is driven by
``chip_smoke.py``; ``tests/test_tpu_compile.py`` compiles the main-path
programs for a described v5e without one.

Test-facing flags mirror the reference harness
(``tests/core/pyspec/eth2spec/test/conftest.py:30-52``):
``--preset``, ``--fork``, ``--disable-bls``, ``--bls-type``.
"""
import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Shared persistent XLA compile cache: the BLS pairing programs are big —
# cache them across pytest runs.
from consensus_specs_tpu.utils.jax_env import setup_compile_cache  # noqa: E402
setup_compile_cache()
# imported up front: the mesh gate (parallel/mesh_state.device_count)
# counts devices only in a process that has imported jax
import jax  # noqa: E402,F401


def pytest_addoption(parser):
    parser.addoption("--preset", action="store", default="minimal",
                     help="preset to run tests with: minimal or mainnet")
    parser.addoption("--fork", action="store", default=None,
                     help="restrict tests to one fork")
    # BLS is disabled by default for suite speed, exactly like the
    # reference's `make test` (Makefile:118-120); @always_bls tests force
    # signature checks regardless, and --enable-bls turns them on
    # everywhere (the reference's citest mode).
    parser.addoption("--enable-bls", action="store_true", default=False,
                     help="verify BLS signatures in every test")
    parser.addoption("--disable-bls", action="store_true", default=False,
                     help="(default) skip BLS checks where tests allow it")
    parser.addoption("--bls-type", action="store", default="py",
                     choices=["py", "jax", "native", "fastest"],
                     help="BLS backend (native = the C library, the "
                          "reference's milagro/arkworks role)")
    parser.addoption("--compiled", action="store_true", default=False,
                     help="run the conformance suite against the markdown-"
                          "compiled spec ladder (make pyspec output) instead "
                          "of the hand-written classes")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _supervisor_isolation():
    """Supervisor breaker/audit state must not leak across tests: a
    differential test that forces repeated guard or injected fallbacks
    would otherwise open a site's circuit breaker and demote that
    engine for every later test in the process (the counter-asserted
    suites would then see spec-path answers).  Reset is a handful of
    dict clears — negligible per test."""
    yield
    from consensus_specs_tpu import supervisor
    supervisor.reset()


@pytest.fixture
def metrics_diff():
    """Counter snapshot/diff fixture (``test_infra/metrics.py``): yields
    the ``counting`` context manager class; keys absent from a measured
    delta read as 0::

        def test_engine_answered(metrics_diff):
            with metrics_diff() as delta:
                spec.get_head(store)
            assert delta["forkchoice.head{path=engine}"] == 1
    """
    from consensus_specs_tpu.test_infra.metrics import counting
    return counting


def _check_speclint_baseline():
    """Deflake guard: the checked-in ratchet file must be sorted and
    duplicate-free, so re-ratchets (`make speclint-baseline`) always
    produce one-line-per-finding diffs.  An unsorted or duplicated
    baseline makes every ratchet a whole-file rewrite — churn that
    hides the real delta — so it fails the session loudly here rather
    than surviving until a confusing review."""
    import json
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "speclint_baseline.json")
    if not os.path.isfile(path):
        return

    def no_dups(pairs):
        seen = set()
        for key, _ in pairs:
            if key in seen:
                raise AssertionError(
                    f"speclint_baseline.json has a duplicate key: {key!r}"
                    " — deduplicate it (json.load would silently keep "
                    "one and the ratchet count would flap)")
            seen.add(key)
        return dict(pairs)

    with open(path) as f:
        data = json.load(f, object_pairs_hook=no_dups)
    keys = list(data.get("counts", {}))
    assert keys == sorted(keys), (
        "speclint_baseline.json counts are not sorted — run "
        "`make speclint-baseline` (the writer sorts) instead of "
        "editing by hand; unsorted keys turn every re-ratchet into a "
        "whole-file diff")
    assert all(isinstance(n, int) and n >= 1
               for n in data.get("counts", {}).values()), (
        "speclint_baseline.json counts must be positive integers")


def pytest_configure(config):
    # `slow`: excluded from the tier-1 `-m 'not slow'` budget run; still
    # covered by `make citest` / CI (no marker filter there)
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from the fast tier")
    _check_speclint_baseline()
    from consensus_specs_tpu.test_infra import context as ctx
    ctx.DEFAULT_TEST_PRESET = config.getoption("--preset")
    ctx.DEFAULT_BLS_ACTIVE = (config.getoption("--enable-bls")
                              and not config.getoption("--disable-bls"))
    ctx.DEFAULT_BLS_TYPE = config.getoption("--bls-type")
    only_fork = config.getoption("--fork")
    if only_fork:
        ctx.ONLY_FORK = only_fork
    if config.getoption("--compiled"):
        from consensus_specs_tpu.forks import use_compiled_registry
        use_compiled_registry()
