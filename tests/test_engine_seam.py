"""Both spec ladders get their accelerators through one seam,
``forks.install_engines``: a method that the hand-written and the
markdown-compiled class of a fork both resolve carries the same engine
wrappers on both, so neither ladder dispatches an engine the other
does not."""
import pytest

from consensus_specs_tpu import forks
from consensus_specs_tpu.compiler.emit import _FORK_ORDER

# the marker attribute each install sets on the wrapper it puts in place
ENGINE_MARKERS = (
    "_vectorized_epoch_wrapper",    # ops/epoch_kernels
    "_att_prep_wrapper",            # ops/att_prep
    "_committee_shuffle",           # ops/committees
    "_block_kernel",                # ops/block_kernels
    "_fc_accel_wrapper",            # forkchoice/proto_array
    "_das_wrapper",                 # das/engine
    "_obs_span_wrapper",            # obs/instrument (tracing)
)


@pytest.fixture(scope="module")
def ladders():
    """Both registries: the hand-written one, then the compiled one as
    ``use_compiled_registry`` builds it; the process registry is put
    back afterwards."""
    hand = forks.fork_registry()
    saved_cache = dict(forks._spec_cache)
    try:
        forks.use_compiled_registry()
        compiled = forks.fork_registry()
    finally:
        forks._REGISTRY.clear()
        forks._REGISTRY.update(hand)
        forks._spec_cache.clear()
        forks._spec_cache.update(saved_cache)
    return hand, compiled


def _markers(fn):
    """The engine markers on ``fn`` and on every wrapper it encloses."""
    found = set()
    while fn is not None:
        found.update(m for m in ENGINE_MARKERS if getattr(fn, m, False))
        fn = getattr(fn, "__wrapped__", None)
    return found


def _wrapped(cls):
    return {name: _markers(getattr(cls, name)) for name in dir(cls)
            if callable(getattr(cls, name, None))
            and _markers(getattr(cls, name))}


@pytest.mark.parametrize("fork", _FORK_ORDER)
def test_both_ladders_carry_the_same_engine_markers(ladders, fork):
    hand, compiled = (registry[fork] for registry in ladders)
    assert hand is not compiled
    assert not hand.__name__.startswith("Compiled")
    assert compiled.__name__.startswith("Compiled")
    hand_wrapped, compiled_wrapped = _wrapped(hand), _wrapped(compiled)
    # the seam is live on both ladders, engines and spans alike
    for wrapped in (hand_wrapped, compiled_wrapped):
        assert "_obs_span_wrapper" in wrapped["state_transition"]
        assert "_committee_shuffle" in wrapped["get_beacon_committee"]
        assert "_vectorized_epoch_wrapper" in wrapped["process_slashings"]
        assert "_fc_accel_wrapper" in wrapped["get_head"]
    shared = [name for name in sorted(set(hand_wrapped) | set(compiled_wrapped))
              if hasattr(hand, name) and hasattr(compiled, name)]
    assert shared
    drift = {name: (sorted(_markers(getattr(hand, name))),
                    sorted(_markers(getattr(compiled, name))))
             for name in shared
             if _markers(getattr(hand, name))
             != _markers(getattr(compiled, name))}
    assert not drift, drift
