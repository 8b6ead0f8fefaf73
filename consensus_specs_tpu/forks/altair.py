"""Altair fork: sync committees, participation-flag accounting, inactivity
scores.

Behavioral source: ``specs/altair/beacon-chain.md`` (constants ~:60, new
containers ~:120, helpers ``get_next_sync_committee_indices`` :275,
``process_sync_aggregate`` :535, flag-based epoch accounting
:300-530), ``specs/altair/bls.md`` (eth_aggregate_pubkeys :25,
eth_fast_aggregate_verify :61) and ``specs/altair/fork.md``
(``upgrade_to_altair`` :77, ``translate_participation`` :61).

Fork inheritance = class inheritance over :class:`Phase0Spec`; only the
altair deltas live here (the reference gets the same effect from markdown
dict-merge, ``pysetup/helpers.py:222-247``).
"""
from consensus_specs_tpu.utils.ssz import (
    hash_tree_root, uint8, uint64, Bytes32,
    Bitvector, Bitlist, Vector, List, Container,
)  # noqa: F401 (compiled-spec namespace)
from consensus_specs_tpu.utils import bls
from . import register_fork
from .phase0 import Phase0Spec
from .light_client import LightClientMixin
from .validator_guide import SyncDutiesMixin
from .base_types import (
    Slot, Epoch, ValidatorIndex, Gwei, Root, Version, BLSPubkey, BLSSignature,
    ParticipationFlags, GENESIS_EPOCH,
    DOMAIN_SYNC_COMMITTEE, DOMAIN_SYNC_COMMITTEE_SELECTION_PROOF,
    DOMAIN_CONTRIBUTION_AND_PROOF,
)  # noqa: F401 (compiled-spec namespace)

# incentivization weights (specs/altair/beacon-chain.md "Incentivization")
TIMELY_SOURCE_FLAG_INDEX = 0
TIMELY_TARGET_FLAG_INDEX = 1
TIMELY_HEAD_FLAG_INDEX = 2
TIMELY_SOURCE_WEIGHT = uint64(14)
TIMELY_TARGET_WEIGHT = uint64(26)
TIMELY_HEAD_WEIGHT = uint64(14)
SYNC_REWARD_WEIGHT = uint64(2)
PROPOSER_WEIGHT = uint64(8)
WEIGHT_DENOMINATOR = uint64(64)
PARTICIPATION_FLAG_WEIGHTS = [
    TIMELY_SOURCE_WEIGHT, TIMELY_TARGET_WEIGHT, TIMELY_HEAD_WEIGHT]

G2_POINT_AT_INFINITY = BLSSignature(b"\xc0" + b"\x00" * 95)


@register_fork("altair")
class AltairSpec(SyncDutiesMixin, LightClientMixin, Phase0Spec):
    fork = "altair"
    previous_fork = "phase0"

    TIMELY_SOURCE_FLAG_INDEX = TIMELY_SOURCE_FLAG_INDEX
    TIMELY_TARGET_FLAG_INDEX = TIMELY_TARGET_FLAG_INDEX
    TIMELY_HEAD_FLAG_INDEX = TIMELY_HEAD_FLAG_INDEX
    TIMELY_SOURCE_WEIGHT = TIMELY_SOURCE_WEIGHT
    TIMELY_TARGET_WEIGHT = TIMELY_TARGET_WEIGHT
    TIMELY_HEAD_WEIGHT = TIMELY_HEAD_WEIGHT
    SYNC_REWARD_WEIGHT = SYNC_REWARD_WEIGHT
    PROPOSER_WEIGHT = PROPOSER_WEIGHT
    WEIGHT_DENOMINATOR = WEIGHT_DENOMINATOR
    PARTICIPATION_FLAG_WEIGHTS = PARTICIPATION_FLAG_WEIGHTS
    G2_POINT_AT_INFINITY = G2_POINT_AT_INFINITY
    DOMAIN_SYNC_COMMITTEE = DOMAIN_SYNC_COMMITTEE
    DOMAIN_SYNC_COMMITTEE_SELECTION_PROOF = DOMAIN_SYNC_COMMITTEE_SELECTION_PROOF
    DOMAIN_CONTRIBUTION_AND_PROOF = DOMAIN_CONTRIBUTION_AND_PROOF
    ParticipationFlags = ParticipationFlags

    # -- type construction ---------------------------------------------------

    def _build_types(self):
        # sync-committee containers must exist before the base builder runs,
        # because it consults the overridden _block_body_fields/_state_fields
        S = self

        class SyncAggregate(Container):
            sync_committee_bits: Bitvector[S.SYNC_COMMITTEE_SIZE]
            sync_committee_signature: BLSSignature

        class SyncCommittee(Container):
            pubkeys: Vector[BLSPubkey, S.SYNC_COMMITTEE_SIZE]
            aggregate_pubkey: BLSPubkey

        self.SyncAggregate = SyncAggregate
        self.SyncCommittee = SyncCommittee
        super()._build_types()
        # light-client containers need BeaconState/BeaconBlockHeader built
        self._build_light_client_types()
        self._build_sync_duty_types()

    def _block_body_fields(self, t) -> dict:
        fields = super()._block_body_fields(t)
        fields["sync_aggregate"] = self.SyncAggregate
        return fields

    def _state_fields(self, t) -> dict:
        """Altair BeaconState layout: pending attestations are replaced by
        participation lists (same position), with inactivity scores and the
        two sync committees appended at the tail."""
        S = self
        fields = super()._state_fields(t)
        out = {}
        for k, v in fields.items():
            if k == "previous_epoch_attestations":
                out["previous_epoch_participation"] = \
                    List[ParticipationFlags, S.VALIDATOR_REGISTRY_LIMIT]
                out["current_epoch_participation"] = \
                    List[ParticipationFlags, S.VALIDATOR_REGISTRY_LIMIT]
            elif k == "current_epoch_attestations":
                continue
            else:
                out[k] = v
        out["inactivity_scores"] = List[uint64, S.VALIDATOR_REGISTRY_LIMIT]
        out["current_sync_committee"] = self.SyncCommittee
        out["next_sync_committee"] = self.SyncCommittee
        return out

    # -- crypto wrappers (specs/altair/bls.md) ------------------------------

    def eth_aggregate_pubkeys(self, pubkeys):
        """bls.md:25 - aggregate of 1+ pubkeys (asserts non-empty)."""
        assert len(pubkeys) > 0
        return bls.AggregatePKs(pubkeys)

    def eth_fast_aggregate_verify(self, pubkeys, message, signature) -> bool:
        """bls.md:61 - empty set + infinity signature verifies True."""
        if len(pubkeys) == 0 and signature == G2_POINT_AT_INFINITY:
            return True
        return bls.FastAggregateVerify(pubkeys, message, signature)

    # -- participation flags ------------------------------------------------

    def add_flag(self, flags, flag_index):
        return ParticipationFlags(flags | (2 ** flag_index))

    def has_flag(self, flags, flag_index) -> bool:
        flag = 2 ** flag_index
        return flags & flag == flag

    # -- sync committee selection (beacon-chain.md:275) ---------------------

    def get_next_sync_committee_indices(self, state):
        """Seeded effective-balance-weighted sampling via shuffled indices."""
        epoch = self.Epoch(self.get_current_epoch(state) + 1)
        MAX_RANDOM_BYTE = 2 ** 8 - 1
        active_validator_indices = self.get_active_validator_indices(state, epoch)
        active_validator_count = uint64(len(active_validator_indices))
        seed = self.get_seed(state, epoch, DOMAIN_SYNC_COMMITTEE)
        i = 0
        sync_committee_indices = []
        while len(sync_committee_indices) < self.SYNC_COMMITTEE_SIZE:
            shuffled_index = self.compute_shuffled_index(
                uint64(i % active_validator_count), active_validator_count, seed)
            candidate_index = active_validator_indices[shuffled_index]
            random_byte = self.hash(
                seed + self.uint_to_bytes(uint64(i // 32)))[i % 32]
            effective_balance = state.validators[candidate_index].effective_balance
            if effective_balance * MAX_RANDOM_BYTE >= \
                    self.MAX_EFFECTIVE_BALANCE * random_byte:
                sync_committee_indices.append(candidate_index)
            i += 1
        return sync_committee_indices

    def get_next_sync_committee(self, state):
        indices = self.get_next_sync_committee_indices(state)
        pubkeys = [state.validators[index].pubkey for index in indices]
        aggregate_pubkey = self.eth_aggregate_pubkeys(pubkeys)
        return self.SyncCommittee(pubkeys=pubkeys,
                                  aggregate_pubkey=aggregate_pubkey)

    # -- participation / reward helpers -------------------------------------

    def get_base_reward_per_increment(self, state):
        return Gwei(self.EFFECTIVE_BALANCE_INCREMENT
                    * self.BASE_REWARD_FACTOR
                    // self.integer_squareroot(self.get_total_active_balance(state)))

    def get_base_reward(self, state, index):
        """Altair redefinition (beacon-chain.md Participation-flags rewards)."""
        increments = (state.validators[index].effective_balance
                      // self.EFFECTIVE_BALANCE_INCREMENT)
        return Gwei(increments * self.get_base_reward_per_increment(state))

    def get_unslashed_participating_indices(self, state, flag_index, epoch):
        assert epoch in (self.get_previous_epoch(state),
                         self.get_current_epoch(state))
        if epoch == self.get_current_epoch(state):
            epoch_participation = state.current_epoch_participation
        else:
            epoch_participation = state.previous_epoch_participation
        active_validator_indices = self.get_active_validator_indices(state, epoch)
        participating_indices = [
            i for i in active_validator_indices
            if self.has_flag(epoch_participation[i], flag_index)]
        return set(
            self.filter_out_slashed(state, participating_indices))

    def filter_out_slashed(self, state, indices):
        return [index for index in indices
                if not state.validators[index].slashed]

    def get_attestation_participation_flag_indices(self, state, data,
                                                   inclusion_delay):
        """Flags an attestation earns given its correctness + timeliness."""
        if data.target.epoch == self.get_current_epoch(state):
            justified_checkpoint = state.current_justified_checkpoint
        else:
            justified_checkpoint = state.previous_justified_checkpoint
        is_matching_source = data.source == justified_checkpoint
        is_matching_target = is_matching_source and bytes(data.target.root) == \
            bytes(self.get_block_root(state, data.target.epoch))
        is_matching_head = is_matching_target and \
            bytes(data.beacon_block_root) == \
            bytes(self.get_block_root_at_slot(state, data.slot))
        assert is_matching_source

        participation_flag_indices = []
        if is_matching_source and inclusion_delay <= \
                self.integer_squareroot(self.SLOTS_PER_EPOCH):
            participation_flag_indices.append(TIMELY_SOURCE_FLAG_INDEX)
        if is_matching_target and inclusion_delay <= self.SLOTS_PER_EPOCH:
            participation_flag_indices.append(TIMELY_TARGET_FLAG_INDEX)
        if is_matching_head and inclusion_delay == \
                self.MIN_ATTESTATION_INCLUSION_DELAY:
            participation_flag_indices.append(TIMELY_HEAD_FLAG_INDEX)
        return participation_flag_indices

    def get_flag_index_deltas(self, state, flag_index):
        """Reward/penalty deltas for one participation flag."""
        rewards = [Gwei(0)] * len(state.validators)
        penalties = [Gwei(0)] * len(state.validators)
        previous_epoch = self.get_previous_epoch(state)
        unslashed_participating_indices = \
            self.get_unslashed_participating_indices(state, flag_index,
                                                     previous_epoch)
        weight = PARTICIPATION_FLAG_WEIGHTS[flag_index]
        unslashed_participating_balance = self.get_total_balance(
            state, unslashed_participating_indices)
        unslashed_participating_increments = (
            unslashed_participating_balance // self.EFFECTIVE_BALANCE_INCREMENT)
        active_increments = (self.get_total_active_balance(state)
                             // self.EFFECTIVE_BALANCE_INCREMENT)
        for index in self.get_eligible_validator_indices(state):
            base_reward = self.get_base_reward(state, index)
            if index in unslashed_participating_indices:
                if not self.is_in_inactivity_leak(state):
                    reward_numerator = (base_reward * weight
                                        * unslashed_participating_increments)
                    rewards[index] += Gwei(reward_numerator
                                           // (active_increments
                                               * WEIGHT_DENOMINATOR))
            elif flag_index != TIMELY_HEAD_FLAG_INDEX:
                penalties[index] += Gwei(base_reward * weight
                                         // WEIGHT_DENOMINATOR)
        return rewards, penalties

    def get_inactivity_penalty_deltas(self, state):
        """Altair inactivity penalties via inactivity scores."""
        rewards = [Gwei(0)] * len(state.validators)
        penalties = [Gwei(0)] * len(state.validators)
        previous_epoch = self.get_previous_epoch(state)
        matching_target_indices = self.get_unslashed_participating_indices(
            state, TIMELY_TARGET_FLAG_INDEX, previous_epoch)
        for index in self.get_eligible_validator_indices(state):
            if index not in matching_target_indices:
                penalty_numerator = (state.validators[index].effective_balance
                                     * state.inactivity_scores[index])
                penalty_denominator = (self.config.INACTIVITY_SCORE_BIAS
                                       * self.INACTIVITY_PENALTY_QUOTIENT_ALTAIR)
                penalties[index] += Gwei(penalty_numerator // penalty_denominator)
        return rewards, penalties

    # -- mutators ------------------------------------------------------------

    def slash_validator(self, state, slashed_index, whistleblower_index=None):
        """Altair variant: different slashing quotient + proposer reward
        weighting (beacon-chain.md Modified slash_validator)."""
        epoch = self.get_current_epoch(state)
        self.initiate_validator_exit(state, slashed_index)
        validator = state.validators[slashed_index]
        validator.slashed = True
        validator.withdrawable_epoch = max(
            validator.withdrawable_epoch,
            self.Epoch(epoch + self.EPOCHS_PER_SLASHINGS_VECTOR))
        state.slashings[epoch % self.EPOCHS_PER_SLASHINGS_VECTOR] += \
            validator.effective_balance
        slashing_penalty = (validator.effective_balance
                            // self.MIN_SLASHING_PENALTY_QUOTIENT_ALTAIR)
        self.decrease_balance(state, slashed_index, slashing_penalty)

        proposer_index = self.get_beacon_proposer_index(state)
        if whistleblower_index is None:
            whistleblower_index = proposer_index
        whistleblower_reward = Gwei(validator.effective_balance
                                    // self.WHISTLEBLOWER_REWARD_QUOTIENT)
        proposer_reward = Gwei(whistleblower_reward * PROPOSER_WEIGHT
                               // WEIGHT_DENOMINATOR)
        self.increase_balance(state, proposer_index, proposer_reward)
        self.increase_balance(state, whistleblower_index,
                              Gwei(whistleblower_reward - proposer_reward))

    # -- block processing ----------------------------------------------------

    def process_block(self, state, block):
        # Same batched-signature discipline as phase0.process_block; the
        # sync-aggregate verify (<=512 pubkeys) joins the block batch too.
        with bls.batched_verification() as batch:
            self.process_block_header(state, block)
            self.process_randao(state, block.body)
            self.process_eth1_data(state, block.body)
            self.process_operations(state, block.body)
            self.process_sync_aggregate(state, block.body.sync_aggregate)
        batch.assert_valid()

    def process_attestation(self, state, attestation):
        """Altair rewrite: flags + immediate proposer reward."""
        data = attestation.data
        assert data.target.epoch in (self.get_previous_epoch(state),
                                     self.get_current_epoch(state))
        assert data.target.epoch == self.compute_epoch_at_slot(data.slot)
        assert (data.slot + self.MIN_ATTESTATION_INCLUSION_DELAY <= state.slot
                <= data.slot + self.SLOTS_PER_EPOCH)
        assert data.index < self.get_committee_count_per_slot(state,
                                                              data.target.epoch)

        committee = self.get_beacon_committee(state, data.slot, data.index)
        assert len(attestation.aggregation_bits) == len(committee)

        participation_flag_indices = \
            self.get_attestation_participation_flag_indices(
                state, data, state.slot - data.slot)

        assert self.is_valid_indexed_attestation(
            state, self.get_indexed_attestation(state, attestation))

        if data.target.epoch == self.get_current_epoch(state):
            epoch_participation = state.current_epoch_participation
        else:
            epoch_participation = state.previous_epoch_participation

        proposer_reward_numerator = 0
        for index in self.get_attesting_indices(
                state, data, attestation.aggregation_bits):
            for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
                if flag_index in participation_flag_indices and \
                        not self.has_flag(epoch_participation[index], flag_index):
                    epoch_participation[index] = self.add_flag(
                        epoch_participation[index], flag_index)
                    proposer_reward_numerator += \
                        self.get_base_reward(state, index) * weight

        proposer_reward_denominator = ((WEIGHT_DENOMINATOR - PROPOSER_WEIGHT)
                                       * WEIGHT_DENOMINATOR // PROPOSER_WEIGHT)
        proposer_reward = Gwei(proposer_reward_numerator
                               // proposer_reward_denominator)
        self.increase_balance(state, self.get_beacon_proposer_index(state),
                              proposer_reward)

    def add_validator_to_registry(self, state, pubkey,
                                  withdrawal_credentials, amount):
        super().add_validator_to_registry(state, pubkey,
                                          withdrawal_credentials, amount)
        state.previous_epoch_participation.append(ParticipationFlags(0))
        state.current_epoch_participation.append(ParticipationFlags(0))
        state.inactivity_scores.append(uint64(0))

    def process_sync_aggregate(self, state, sync_aggregate):
        """beacon-chain.md:535 - one aggregate verify over <=512 pubkeys,
        then the per-participant balance loop."""
        committee_pubkeys = state.current_sync_committee.pubkeys
        participant_pubkeys = [
            pubkey for pubkey, bit in
            zip(committee_pubkeys, sync_aggregate.sync_committee_bits) if bit]
        previous_slot = max(state.slot, Slot(1)) - Slot(1)
        domain = self.get_domain(state, DOMAIN_SYNC_COMMITTEE,
                                 self.compute_epoch_at_slot(previous_slot))
        signing_root = self.compute_signing_root(
            self.get_block_root_at_slot(state, previous_slot), domain)
        assert self.eth_fast_aggregate_verify(
            participant_pubkeys, signing_root,
            sync_aggregate.sync_committee_signature)

        total_active_increments = (self.get_total_active_balance(state)
                                   // self.EFFECTIVE_BALANCE_INCREMENT)
        total_base_rewards = Gwei(self.get_base_reward_per_increment(state)
                                  * total_active_increments)
        max_participant_rewards = Gwei(total_base_rewards * SYNC_REWARD_WEIGHT
                                       // WEIGHT_DENOMINATOR
                                       // self.SLOTS_PER_EPOCH)
        participant_reward = Gwei(max_participant_rewards
                                  // self.SYNC_COMMITTEE_SIZE)
        proposer_reward = Gwei(participant_reward * PROPOSER_WEIGHT
                               // (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT))

        all_pubkeys = [v.pubkey for v in state.validators]
        committee_indices = [
            ValidatorIndex(all_pubkeys.index(pubkey))
            for pubkey in state.current_sync_committee.pubkeys]
        for participant_index, participation_bit in zip(
                committee_indices, sync_aggregate.sync_committee_bits):
            if participation_bit:
                self.increase_balance(state, participant_index,
                                      participant_reward)
                self.increase_balance(
                    state, self.get_beacon_proposer_index(state),
                    proposer_reward)
            else:
                self.decrease_balance(state, participant_index,
                                      participant_reward)

    # -- epoch processing ----------------------------------------------------

    def process_epoch(self, state):
        self.process_justification_and_finalization(state)
        self.process_inactivity_updates(state)
        self.process_rewards_and_penalties(state)
        self.process_registry_updates(state)
        self.process_slashings(state)
        self.process_eth1_data_reset(state)
        self.process_effective_balance_updates(state)
        self.process_slashings_reset(state)
        self.process_randao_mixes_reset(state)
        self.process_historical_roots_update(state)
        self.process_participation_flag_updates(state)
        self.process_sync_committee_updates(state)

    def process_justification_and_finalization(self, state):
        """Altair variant driven by target-flag participation."""
        if self.get_current_epoch(state) <= GENESIS_EPOCH + 1:
            return
        previous_indices = self.get_unslashed_participating_indices(
            state, TIMELY_TARGET_FLAG_INDEX, self.get_previous_epoch(state))
        current_indices = self.get_unslashed_participating_indices(
            state, TIMELY_TARGET_FLAG_INDEX, self.get_current_epoch(state))
        total_active_balance = self.get_total_active_balance(state)
        previous_target_balance = self.get_total_balance(state, previous_indices)
        current_target_balance = self.get_total_balance(state, current_indices)
        self.weigh_justification_and_finalization(
            state, total_active_balance,
            previous_target_balance, current_target_balance)

    def process_inactivity_updates(self, state):
        if self.get_current_epoch(state) == GENESIS_EPOCH:
            return
        participating = self.get_unslashed_participating_indices(
            state, TIMELY_TARGET_FLAG_INDEX, self.get_previous_epoch(state))
        for index in self.get_eligible_validator_indices(state):
            if index in participating:
                state.inactivity_scores[index] -= min(
                    uint64(1), state.inactivity_scores[index])
            else:
                state.inactivity_scores[index] += \
                    self.config.INACTIVITY_SCORE_BIAS
            if not self.is_in_inactivity_leak(state):
                state.inactivity_scores[index] -= min(
                    self.config.INACTIVITY_SCORE_RECOVERY_RATE,
                    state.inactivity_scores[index])

    def process_rewards_and_penalties(self, state):
        if self.get_current_epoch(state) == GENESIS_EPOCH:
            return
        flag_deltas = [self.get_flag_index_deltas(state, flag_index)
                       for flag_index in range(len(PARTICIPATION_FLAG_WEIGHTS))]
        deltas = flag_deltas + [self.get_inactivity_penalty_deltas(state)]
        for (rewards, penalties) in deltas:
            for index in range(len(state.validators)):
                self.increase_balance(state, ValidatorIndex(index),
                                      rewards[index])
                self.decrease_balance(state, ValidatorIndex(index),
                                      penalties[index])

    def process_slashings(self, state):
        epoch = self.get_current_epoch(state)
        total_balance = self.get_total_active_balance(state)
        adjusted_total_slashing_balance = min(
            sum(state.slashings) * self.PROPORTIONAL_SLASHING_MULTIPLIER_ALTAIR,
            total_balance)
        for index, validator in enumerate(state.validators):
            if validator.slashed and epoch + self.EPOCHS_PER_SLASHINGS_VECTOR \
                    // 2 == validator.withdrawable_epoch:
                increment = self.EFFECTIVE_BALANCE_INCREMENT
                penalty_numerator = (validator.effective_balance // increment
                                     * adjusted_total_slashing_balance)
                penalty = penalty_numerator // total_balance * increment
                self.decrease_balance(state, ValidatorIndex(index), penalty)

    def process_participation_flag_updates(self, state):
        state.previous_epoch_participation = state.current_epoch_participation
        state.current_epoch_participation = type(
            state.current_epoch_participation)(
                *[ParticipationFlags(0) for _ in range(len(state.validators))])

    def process_sync_committee_updates(self, state):
        next_epoch = self.get_current_epoch(state) + self.Epoch(1)
        if next_epoch % self.EPOCHS_PER_SYNC_COMMITTEE_PERIOD == 0:
            state.current_sync_committee = state.next_sync_committee
            state.next_sync_committee = self.get_next_sync_committee(state)

    def process_participation_record_updates(self, state):
        raise AttributeError("phase0-only (replaced by participation flags)")

    # -- fork upgrade (specs/altair/fork.md) ---------------------------------

    def translate_participation(self, post, pending_attestations):
        """fork.md:61 - re-grant flags for pending phase0 attestations."""
        for attestation in pending_attestations:
            data = attestation.data
            inclusion_delay = attestation.inclusion_delay
            participation_flag_indices = \
                self.get_attestation_participation_flag_indices(
                    post, data, inclusion_delay)
            epoch_participation = post.previous_epoch_participation
            # get_attesting_indices is inherited unchanged from phase0
            for index in self.get_attesting_indices(
                    post, data, attestation.aggregation_bits):
                for flag_index in participation_flag_indices:
                    epoch_participation[index] = self.add_flag(
                        epoch_participation[index], flag_index)

    def upgrade_to_altair(self, pre):
        """fork.md:77 - phase0 BeaconState -> altair BeaconState."""
        epoch = self.get_current_epoch(pre)
        post = self.BeaconState(
            genesis_time=pre.genesis_time,
            genesis_validators_root=pre.genesis_validators_root,
            slot=pre.slot,
            fork=self.Fork(
                previous_version=pre.fork.current_version,
                current_version=self.config.ALTAIR_FORK_VERSION,
                epoch=epoch,
            ),
            latest_block_header=pre.latest_block_header,
            block_roots=pre.block_roots,
            state_roots=pre.state_roots,
            historical_roots=pre.historical_roots,
            eth1_data=pre.eth1_data,
            eth1_data_votes=pre.eth1_data_votes,
            eth1_deposit_index=pre.eth1_deposit_index,
            validators=pre.validators,
            balances=pre.balances,
            randao_mixes=pre.randao_mixes,
            slashings=pre.slashings,
            previous_epoch_participation=[
                ParticipationFlags(0) for _ in range(len(pre.validators))],
            current_epoch_participation=[
                ParticipationFlags(0) for _ in range(len(pre.validators))],
            justification_bits=pre.justification_bits,
            previous_justified_checkpoint=pre.previous_justified_checkpoint,
            current_justified_checkpoint=pre.current_justified_checkpoint,
            finalized_checkpoint=pre.finalized_checkpoint,
            inactivity_scores=[uint64(0) for _ in range(len(pre.validators))],
        )
        self.translate_participation(post, pre.previous_epoch_attestations)
        sync_committee = self.get_next_sync_committee(post)
        post.current_sync_committee = sync_committee
        post.next_sync_committee = self.get_next_sync_committee(post)
        return post

    def initialize_beacon_state_from_eth1(self, eth1_block_hash,
                                          eth1_timestamp, deposits):
        """Altair testing variant (``specs/altair/beacon-chain.md``
        Testing section): genesis at the altair fork version, sync
        committees pre-filled (current == next at genesis)."""
        state = super().initialize_beacon_state_from_eth1(
            eth1_block_hash, eth1_timestamp, deposits)
        state.fork.previous_version = self.config.ALTAIR_FORK_VERSION
        state.fork.current_version = self.config.ALTAIR_FORK_VERSION
        state.current_sync_committee = self.get_next_sync_committee(state)
        state.next_sync_committee = self.get_next_sync_committee(state)
        return state

    # -- mock genesis hook ---------------------------------------------------

    def post_mock_genesis(self, state):
        """Fill altair-only genesis fields for harness-built states."""
        for _ in range(len(state.validators)):
            state.previous_epoch_participation.append(ParticipationFlags(0))
            state.current_epoch_participation.append(ParticipationFlags(0))
            state.inactivity_scores.append(uint64(0))
        sync_committee = self.get_next_sync_committee(state)
        state.current_sync_committee = sync_committee
        state.next_sync_committee = self.get_next_sync_committee(state)
