"""Block operations whose per-block cost grows with the registry, without
the per-registry work the spec bodies repeat.

At 2^20 active validators a mainnet block's 128 attestations name 65,536
committee members, and the sync aggregate resolves 512 pubkeys against
the whole registry.  The spec bodies pay, per member and flag,
``get_base_reward``, whose ``get_base_reward_per_increment`` takes the
integer square root of the total active balance every call; and
``process_sync_aggregate`` lists all 2^20 registry pubkeys every block.
Both answers are constant where the spec recomputes them:

* ``get_base_reward_per_increment`` reads the state only through
  ``get_total_active_balance``, which is cached on (registry root,
  current epoch); its answer is memoized on the same key, and the spec's
  ``process_attestation`` runs as written.
* ``process_sync_aggregate``: the first registry index of each pubkey,
  cached under ``_caches["pubkey_index"]`` keyed on the registry root.

:func:`install_block_kernels` installs both from outside, through
``forks.install_engines`` like ``att_prep`` and the epoch engine, so the
hand-written and the markdown-compiled ladders run them; the spec bodies
stay as written.
"""
import functools

from consensus_specs_tpu.forks.base_types import ValidatorIndex
from consensus_specs_tpu.utils.lru import LRUDict
from consensus_specs_tpu.utils.ssz import hash_tree_root


def _cache(spec, name, size):
    cache = spec._caches.get(name)
    if cache is None:
        cache = spec._caches[name] = LRUDict(size)
    return cache


def _memo_base_reward_per_increment(fn):
    """``fn`` memoized on the key of the total-active-balance cache."""
    @functools.wraps(fn)
    def per_increment(self, state):
        # (registry root, current epoch), in plain ints: the spec loop
        # asks once per attester and flag
        key = (state.validators.hash_tree_root(),
               int(state.slot) // int(self.SLOTS_PER_EPOCH))
        cache = _cache(self, "base_reward_per_increment", 128)
        value = cache.get(key)
        if value is None:
            value = cache[key] = fn(self, state)
        return value
    per_increment._block_kernel = True
    return per_increment


def _pubkey_index(spec, state) -> dict:
    """The first registry index of every pubkey, per registry root."""
    key = bytes(hash_tree_root(state.validators))
    cache = _cache(spec, "pubkey_index", 2)
    index = cache.get(key)
    if index is None:
        index = {}
        for i, v in enumerate(state.validators):
            index.setdefault(bytes(v.pubkey), i)
        cache[key] = index
    return index


def process_sync_aggregate(spec, state, sync_aggregate):
    """altair ``process_sync_aggregate`` (beacon-chain.md)."""
    committee_pubkeys = state.current_sync_committee.pubkeys
    participant_pubkeys = [
        pubkey for pubkey, bit in
        zip(committee_pubkeys, sync_aggregate.sync_committee_bits) if bit]
    previous_slot = max(int(state.slot), 1) - 1
    domain = spec.get_domain(state, spec.DOMAIN_SYNC_COMMITTEE,
                             spec.compute_epoch_at_slot(previous_slot))
    signing_root = spec.compute_signing_root(
        spec.get_block_root_at_slot(state, previous_slot), domain)
    assert spec.eth_fast_aggregate_verify(
        participant_pubkeys, signing_root,
        sync_aggregate.sync_committee_signature)

    total_active_increments = (spec.get_total_active_balance(state)
                               // spec.EFFECTIVE_BALANCE_INCREMENT)
    total_base_rewards = (spec.get_base_reward_per_increment(state)
                          * total_active_increments)
    max_participant_rewards = (total_base_rewards * spec.SYNC_REWARD_WEIGHT
                               // spec.WEIGHT_DENOMINATOR
                               // spec.SLOTS_PER_EPOCH)
    participant_reward = max_participant_rewards // spec.SYNC_COMMITTEE_SIZE
    proposer_reward = (participant_reward * spec.PROPOSER_WEIGHT
                       // (spec.WEIGHT_DENOMINATOR - spec.PROPOSER_WEIGHT))

    index_of = _pubkey_index(spec, state)
    committee_indices = []
    for pubkey in committee_pubkeys:
        i = index_of.get(bytes(pubkey))
        if i is None:           # the spec's list.index raises here
            raise ValueError("sync committee pubkey not in the registry")
        committee_indices.append(ValidatorIndex(i))
    proposer = spec.get_beacon_proposer_index(state)
    for participant_index, participation_bit in zip(
            committee_indices, sync_aggregate.sync_committee_bits):
        if participation_bit:
            spec.increase_balance(state, participant_index,
                                  participant_reward)
            spec.increase_balance(state, proposer, proposer_reward)
        else:
            spec.decrease_balance(state, participant_index,
                                  participant_reward)


def install_block_kernels(cls) -> None:
    """Install the ``get_base_reward_per_increment`` memo and this
    module's ``process_sync_aggregate`` on ``cls``'s own methods, once.
    Applied to every fork class of both ladders by
    ``forks.install_engines``."""
    fn = cls.__dict__.get("get_base_reward_per_increment")
    if fn is not None and not getattr(fn, "_block_kernel", False):
        setattr(cls, "get_base_reward_per_increment",
                _memo_base_reward_per_increment(fn))

    fn = cls.__dict__.get("process_sync_aggregate")
    if fn is not None and not getattr(fn, "_block_kernel", False):
        @functools.wraps(fn)
        def sync_aggregate(self, state, sync_aggregate):
            return process_sync_aggregate(self, state, sync_aggregate)
        sync_aggregate._block_kernel = True
        setattr(cls, "process_sync_aggregate", sync_aggregate)
