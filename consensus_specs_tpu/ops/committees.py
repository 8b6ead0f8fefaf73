"""Whole-list committee shuffling: one swap-or-not pass over the active
set per (attester seed, active set), committees sliced out of it.

The spec's ``get_beacon_committee`` builds every member through the
per-index ``compute_shuffled_index`` (``SHUFFLE_ROUND_COUNT`` rounds,
two hashes each).  At 2^20 active validators a block's 128 attestations
name 65,536 members, so per-index shuffling costs seconds a block.
Clients instead shuffle the whole active list once per epoch seed and
slice.  :func:`shuffled_positions` does that in numpy: round by round,
every index at once, with the round's ``ceil(n / 256)`` source hashes
computed once and shared by all the positions they cover.  It returns
exactly ``[compute_shuffled_index(i, n, seed) for i in range(n)]``.

:func:`install_committee_shuffle` replaces ``get_beacon_committee`` from
outside (through ``forks.install_engines``, like ``att_prep`` and the
epoch engine), so both the hand-written and the markdown-compiled ladders
slice the same list.
The shuffle is cached on the spec instance under ``_caches["shuffling"]``,
keyed on what ``compute_committee`` reads: the attester seed
(``get_seed(state, epoch, DOMAIN_BEACON_ATTESTER)``), the epoch, and the
active set (the registry root with the epoch determines it, and keys
``get_active_validator_indices`` the same way).  Per-block fields such
as the randao mixes root are not in the key, so every block of an epoch
reuses one shuffle.  ``compute_shuffled_index`` stays the spec's
definition; ``compute_proposer_index`` still calls it.

Counters: ``st.committee_cache{result=hit|miss}`` per committee lookup
(a miss computes a shuffle) and ``st.shuffles`` per shuffle computed.
Span: ``st.committees`` around every lookup, shuffle included.
"""
import functools

import numpy as np

from consensus_specs_tpu.forks.base_types import (
    DOMAIN_BEACON_ATTESTER, ValidatorIndex)
from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.obs.tracing import span
from consensus_specs_tpu.utils.hash_function import hash
from consensus_specs_tpu.utils.lru import LRUDict
from consensus_specs_tpu.utils.ssz import hash_tree_root

_C_HIT = obs_registry.counter("st.committee_cache").labels(result="hit")
_C_MISS = obs_registry.counter("st.committee_cache").labels(result="miss")
_C_SHUFFLES = obs_registry.counter("st.shuffles").labels()

# shuffles kept per spec instance: the previous, current and next
# epochs' of one chain, and one more for a competing fork
SHUFFLES_KEPT = 4


def shuffled_positions(index_count, seed, rounds) -> np.ndarray:
    """``out[i] == compute_shuffled_index(i, index_count, seed)`` for
    every ``i``, computed for the whole list at once.

    In round ``r`` the spec maps ``i`` to ``flip = (pivot - i) mod n``
    when bit ``position % 8`` of byte ``(position % 256) // 8`` of
    ``hash(seed ‖ r ‖ position // 256)`` is set, with ``position =
    max(i, flip)``.  Laid end to end and unpacked little-endian, the
    round's source hashes hold that bit at offset ``position``."""
    n = int(index_count)
    seed = bytes(seed)
    dtype = np.int64 if n >= 2 ** 31 else np.int32
    idx = np.arange(n, dtype=dtype)
    flip = np.empty_like(idx)
    position = np.empty_like(idx)
    blocks = range((n + 255) // 256)
    for r in range(int(rounds)):
        tag = seed + bytes([r])
        pivot = int.from_bytes(hash(tag)[:8], "little") % n
        bits = np.unpackbits(np.frombuffer(
            b"".join(hash(tag + j.to_bytes(4, "little")) for j in blocks),
            dtype=np.uint8), bitorder="little").view(bool)
        np.subtract(pivot, idx, out=flip)
        np.add(flip, n, out=flip, where=flip < 0)
        np.maximum(idx, flip, out=position)
        np.copyto(idx, flip, where=bits[position])
    return idx


class _Shuffling:
    """One epoch's shuffled active list and the committees sliced from it
    so far (by committee position in the epoch)."""

    __slots__ = ("shuffled", "committees")

    def __init__(self, shuffled):
        self.shuffled = shuffled
        self.committees = {}


def _shuffling(spec, state, epoch) -> _Shuffling:
    seed = spec.get_seed(state, epoch, DOMAIN_BEACON_ATTESTER)
    key = (bytes(seed), int(epoch), bytes(hash_tree_root(state.validators)))
    cache = spec._caches.get("shuffling")
    if cache is None:
        cache = spec._caches["shuffling"] = LRUDict(SHUFFLES_KEPT)
    entry = cache.get(key)
    if entry is not None:
        _C_HIT.add()
        return entry
    _C_MISS.add()
    _C_SHUFFLES.add()
    indices = spec.get_active_validator_indices(state, epoch)
    active = np.fromiter(indices, dtype=np.int64, count=len(indices))
    entry = _Shuffling(active[shuffled_positions(
        len(indices), seed, spec.SHUFFLE_ROUND_COUNT)])
    cache[key] = entry
    return entry


def beacon_committee(spec, state, slot, index):
    """``get_beacon_committee`` (beacon-chain.md:1017) sliced out of the
    epoch's whole-list shuffle: the same members, in the same order, as
    ``compute_committee`` over the active set."""
    with span("st.committees"):
        epoch = spec.compute_epoch_at_slot(slot)
        committees_per_slot = spec.get_committee_count_per_slot(state, epoch)
        entry = _shuffling(spec, state, epoch)
        i = int((slot % spec.SLOTS_PER_EPOCH) * committees_per_slot + index)
        committee = entry.committees.get(i)
        if committee is None:
            count = int(committees_per_slot * spec.SLOTS_PER_EPOCH)
            n = len(entry.shuffled)
            members = entry.shuffled[n * i // count:n * (i + 1) // count]
            committee = tuple(ValidatorIndex(v) for v in members.tolist())
            entry.committees[i] = committee
        # immutable and shared, as the spec-shaped cache returned it
        return committee


def install_committee_shuffle(cls) -> None:
    """Replace ``cls``'s own ``get_beacon_committee`` with
    :func:`beacon_committee`.  Only a method defined on ``cls`` itself is
    replaced, once.  Applied to every fork class of both ladders by
    ``forks.install_engines``."""
    fn = cls.__dict__.get("get_beacon_committee")
    if fn is None or getattr(fn, "_committee_shuffle", False):
        return

    @functools.wraps(fn)
    def get_beacon_committee(self, state, slot, index):
        return beacon_committee(self, state, slot, index)
    get_beacon_committee._committee_shuffle = True
    setattr(cls, "get_beacon_committee", get_beacon_committee)
