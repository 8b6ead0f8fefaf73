"""Per-fork spec runtimes.

Each fork is a class extending the previous fork's class (fork inheritance =
class inheritance, replacing the reference's markdown dict-merge pipeline,
``pysetup/helpers.py:222-247``). ``build_spec(fork, preset)`` instantiates a
fork spec bound to a preset + config; instances are cached like the
reference's ``spec_targets`` (``test/helpers/specs.py:19-26``).
"""
from typing import Dict, Optional, Tuple

_REGISTRY = {}


def install_engines(cls) -> None:
    """Wrap ``cls``'s own spec methods with every accelerator, from
    outside, so the method bodies stay spec-shaped on both ladders.
    Each install touches only methods defined on ``cls`` itself and is
    idempotent; the order is fixed here once: the engine dispatches
    first, the tracing spans last so they enclose the dispatch."""
    from consensus_specs_tpu.das.engine import install_das_accel
    from consensus_specs_tpu.forkchoice.proto_array import \
        install_forkchoice_accel
    from consensus_specs_tpu.obs import install_tracing
    from consensus_specs_tpu.ops.att_prep import install_att_prep
    from consensus_specs_tpu.ops.block_kernels import install_block_kernels
    from consensus_specs_tpu.ops.committees import install_committee_shuffle
    from consensus_specs_tpu.ops.epoch_kernels import install_vectorized_epoch
    install_vectorized_epoch(cls)
    install_att_prep(cls)
    install_committee_shuffle(cls)
    install_block_kernels(cls)
    install_forkchoice_accel(cls)
    install_das_accel(cls)
    install_tracing(cls)


def register_fork(name):
    def deco(cls):
        install_engines(cls)
        _REGISTRY[name] = cls
        cls.fork = name
        return cls
    return deco


def fork_registry() -> Dict[str, type]:
    if not _REGISTRY:
        _import_all()
    return dict(_REGISTRY)


def _import_all():
    import importlib.util
    from . import phase0  # noqa: F401
    for mod in ("altair", "bellatrix", "capella", "deneb",
                "eip6110", "eip7002", "eip7594", "whisk",
                "sharding", "custody_game", "eip6914"):
        # Probe existence first so a real import error inside an existing
        # fork module propagates instead of silently dropping the fork
        # (and silently skipping its whole test suite).
        if importlib.util.find_spec(f"{__name__}.{mod}") is not None:
            __import__(f"{__name__}.{mod}")


_spec_cache: Dict[Tuple[str, str, Optional[frozenset]], object] = {}


def build_spec(fork: str, preset_name: str, config_overrides: Optional[dict] = None):
    """Build (or fetch cached) spec instance for fork × preset."""
    key = (fork, preset_name,
           frozenset(config_overrides.items()) if config_overrides else None)
    spec = _spec_cache.get(key)
    if spec is None:
        from consensus_specs_tpu.config import load_preset, load_config
        registry = fork_registry()
        if fork not in registry:
            raise ValueError(f"unknown fork {fork!r}; have {sorted(registry)}")
        preset = load_preset(preset_name)
        config = load_config(preset_name)
        if config_overrides:
            config = {**config, **config_overrides}
        spec = registry[fork](preset, config, preset_name=preset_name)
        _spec_cache[key] = spec
    return spec


def use_compiled_registry():
    """Swap the registry entries of all nine built forks for the
    markdown-COMPILED
    ladder (``make pyspec`` output, ``compiler/emit.py``), so the same
    conformance suite that exercises the hand-written classes runs
    against the classes built from ``specs/*/beacon-chain.md`` — pytest
    session flag ``--compiled`` (reference analog: the reference suite
    only ever runs the markdown-built pyspec).

    Always recompiles from the markdown first (a couple of seconds of
    pure python) so a green ``--compiled`` run certifies the CURRENT
    spec text, never a stale or half-written generated tree.  The swap
    covers the same 9-fork surface the reference builds
    (``pysetup/spec_builders/__init__.py:12-18``): phase0..deneb plus
    eip6110/eip7002/whisk/eip7594; the recompile also enforces the
    provenance guard (``compiler.emit.verify_provenance``), so a green
    run certifies every spec-logic method came from markdown.
    """
    import importlib
    fork_registry()  # populate before overriding (guard needs it too)
    from consensus_specs_tpu.compiler.emit import (
        main as _compile_all, _FORK_ORDER)
    _compile_all()
    importlib.invalidate_caches()  # compiled/ may have just been created
    for fork in _FORK_ORDER:
        mod = importlib.import_module(f"{__name__}.compiled.{fork}")
        importlib.reload(mod)
        cls = getattr(mod, f"Compiled{fork.capitalize()}Spec")
        install_engines(cls)
        _REGISTRY[fork] = cls
    _spec_cache.clear()
