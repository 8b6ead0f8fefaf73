"""The whole-list committee shuffle (``ops/committees.py``) against the
spec's per-index definition, on both fork ladders, and its cache across
the blocks of an epoch."""
import os
import random
import subprocess
import sys

import pytest

from consensus_specs_tpu.config import load_config, load_preset
from consensus_specs_tpu.forks import build_spec, install_engines
from consensus_specs_tpu.ops import committees
from consensus_specs_tpu.test_infra.metrics import counting

HIT = "st.committee_cache{result=hit}"
MISS = "st.committee_cache{result=miss}"


def _seed(tag) -> bytes:
    return random.Random(repr(tag)).randbytes(32)


@pytest.mark.parametrize("size", [1, 2, 127, 128, 2 ** 14 + 3])
@pytest.mark.parametrize("preset", ["minimal", "mainnet"])
def test_list_shuffle_equals_per_index_definition(size, preset):
    spec = build_spec("phase0", preset)
    rounds = spec.SHUFFLE_ROUND_COUNT
    for k in range(2):
        seed = _seed((size, preset, k))
        got = committees.shuffled_positions(size, seed, rounds).tolist()
        assert sorted(got) == list(range(size))
        # every index where the per-index walk is cheap, a seeded sample
        # of 256 where it is not (90 rounds x 2^14 indices)
        picks = range(size) if size * rounds <= 2 ** 18 else \
            random.Random(k).sample(range(size), 256)
        for i in picks:
            assert got[i] == spec.compute_shuffled_index(i, size, seed), i


def _registry_state(spec, n, rng):
    """A state at epoch 2 of ``n`` validators, every 7th one exited
    before it, so the active set is not ``range(n)``; seeded randao."""
    far = spec.FAR_FUTURE_EPOCH
    validators = [spec.Validator(
        pubkey=i.to_bytes(48, "little"),
        effective_balance=spec.MAX_EFFECTIVE_BALANCE,
        activation_epoch=0,
        exit_epoch=1 if i % 7 == 3 else far,
        withdrawable_epoch=far) for i in range(n)]
    state = spec.BeaconState(
        slot=2 * spec.SLOTS_PER_EPOCH + 1,
        validators=validators,
        balances=[spec.MAX_EFFECTIVE_BALANCE] * n)
    for e in range(8):
        state.randao_mixes[e] = rng.randbytes(32)
    return state


def _per_index_committee(spec, state, slot, index):
    """beacon-chain.md:1017, member by member through
    ``compute_shuffled_index``."""
    epoch = spec.compute_epoch_at_slot(slot)
    per_slot = spec.get_committee_count_per_slot(state, epoch)
    return list(spec.compute_committee(
        indices=spec.get_active_validator_indices(state, epoch),
        seed=spec.get_seed(state, epoch, spec.DOMAIN_BEACON_ATTESTER),
        index=(slot % spec.SLOTS_PER_EPOCH) * per_slot + index,
        count=per_slot * spec.SLOTS_PER_EPOCH))


def _assert_committees_match(spec, state, slots, sample=None):
    epoch = spec.get_current_epoch(state)
    per_slot = int(spec.get_committee_count_per_slot(state, epoch))
    pairs = [(s, c) for s in slots for c in range(per_slot)]
    if sample is not None:
        pairs = random.Random(1).sample(pairs, sample)
    for slot, c in pairs:
        got = spec.get_beacon_committee(state, slot, c)
        assert isinstance(got, tuple)
        assert list(got) == _per_index_committee(spec, state, slot, c), \
            (slot, c)


def _epoch_slots(spec, state):
    start = spec.compute_start_slot_at_epoch(spec.get_current_epoch(state))
    return range(start, start + spec.SLOTS_PER_EPOCH)


@pytest.mark.parametrize("fork", ["phase0", "deneb"])
def test_committees_match_per_index_hand_ladder(fork):
    spec = build_spec(fork, "minimal")
    state = _registry_state(spec, 300, random.Random(fork))
    assert getattr(type(spec).get_beacon_committee, "_committee_shuffle")
    _assert_committees_match(spec, state, _epoch_slots(spec, state))


def test_committees_match_per_index_mainnet_sample():
    spec = build_spec("deneb", "mainnet")
    state = _registry_state(spec, 2 ** 14 + 3, random.Random(5))
    _assert_committees_match(spec, state, _epoch_slots(spec, state),
                             sample=3)


def test_committees_match_per_index_compiled_ladder():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-m", "consensus_specs_tpu.compiler"],
                   check=True, cwd=repo, capture_output=True)
    from consensus_specs_tpu.forks.compiled.phase0 import CompiledPhase0Spec
    from consensus_specs_tpu.forks.compiled.deneb import CompiledDenebSpec
    for klass in CompiledDenebSpec.__mro__:
        if isinstance(klass.__dict__.get("fork"), str):
            install_engines(klass)
    assert CompiledPhase0Spec.get_beacon_committee._committee_shuffle
    spec = CompiledDenebSpec(load_preset("minimal"), load_config("minimal"),
                             preset_name="minimal")
    state = _registry_state(spec, 300, random.Random(9))
    with counting() as delta:
        _assert_committees_match(spec, state, _epoch_slots(spec, state))
    assert delta[MISS] == 1 and delta["st.shuffles"] == 1


def test_cache_hits_across_the_blocks_of_an_epoch():
    """Each block rewrites the randao mix and advances the slot; the
    attester seed of the epoch does not change, so one shuffle serves
    every block.  A new epoch's seed computes a new one."""
    spec = build_spec("deneb", "minimal")
    rng = random.Random(11)
    state = _registry_state(spec, 300, rng)
    spec._caches.pop("shuffling", None)
    epoch = spec.get_current_epoch(state)
    with counting() as delta:
        for _ in range(3):
            for c in range(2):
                spec.get_beacon_committee(state, state.slot - 1, c)
            state.randao_mixes[epoch % spec.EPOCHS_PER_HISTORICAL_VECTOR] = \
                rng.randbytes(32)
            state.slot += 1
    assert delta[MISS] == 1 and delta["st.shuffles"] == 1
    assert delta[HIT] == 5
    with counting() as delta:
        spec.get_beacon_committee(
            state, spec.compute_start_slot_at_epoch(epoch + 1), 0)
    assert delta[MISS] == 1
