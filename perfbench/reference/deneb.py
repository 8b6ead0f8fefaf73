"""A plain deneb state transition for block replay, written from the
consensus specification (``specs/{phase0,altair,bellatrix,capella,
deneb}/beacon-chain.md``) and importing nothing of the program.  It reads
the in-repo preset and config YAML files as data.

It covers what the replay cell's blocks hold: ``process_slots`` inside
an epoch (it refuses an epoch boundary), the block header, withdrawals
and the execution payload, randao, eth1 data, altair-style attestations
(participation flags and the proposer reward) and the sync aggregate.
It asserts every other operation list is empty.  It verifies no
signature: the cell's verdicts come from the generator's truth and from
a tampered block.  ``omit_proposer_reward`` leaves the attestation
proposer reward out (the benchmark's control).

Committees come from a whole-list shuffle (numpy): the per-round
swap-or-not maps of the positions composed into the inverse
permutation, then inverted, which is a different computation from the
per-index walk of ``compute_shuffled_index`` with the same result.
"""
import hashlib
import math
import os
import struct

from . import ssz as S

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "consensus_specs_tpu")
FORKS = ("phase0", "altair", "bellatrix", "capella", "deneb")

# beacon-chain.md constants (not preset values)
DOMAIN_BEACON_PROPOSER = b"\x00\x00\x00\x00"
DOMAIN_BEACON_ATTESTER = b"\x01\x00\x00\x00"
TIMELY_SOURCE, TIMELY_TARGET, TIMELY_HEAD = 0, 1, 2
FLAG_WEIGHTS = (14, 26, 14)
SYNC_REWARD_WEIGHT, PROPOSER_WEIGHT, WEIGHT_DENOMINATOR = 2, 8, 64
ETH1_ADDRESS_WITHDRAWAL_PREFIX = b"\x01"
MAX_RANDOM_BYTE = 2 ** 8 - 1


def _flat_yaml(path) -> dict:
    out = {}
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if ":" not in line:
                continue
            k, v = (x.strip() for x in line.split(":", 1))
            v = v.strip("'\"")
            out[k] = int(v) if v.isdigit() else v
    return out


def load_params(preset) -> dict:
    p = {}
    for fork in FORKS:
        p.update(_flat_yaml(os.path.join(DATA, "presets", preset,
                                         fork + ".yaml")))
    cfg = _flat_yaml(os.path.join(DATA, "configs", preset + ".yaml"))
    p["SECONDS_PER_SLOT"] = cfg["SECONDS_PER_SLOT"]
    return p


def sha256(data) -> bytes:
    return hashlib.sha256(data).digest()


def u64(n) -> bytes:
    return int(n).to_bytes(8, "little")


def types(p) -> dict:
    """The deneb containers of a state and a signed block (phase0 to
    deneb beacon-chain.md, "Containers")."""
    U8, U64, U256, B = S.Uint(1), S.Uint(8), S.Uint(32), S.Boolean()
    R, B48, B96 = S.ByteVector(32), S.ByteVector(48), S.ByteVector(96)
    C = S.Container
    Fork = C("Fork", [("previous_version", S.ByteVector(4)),
                      ("current_version", S.ByteVector(4)), ("epoch", U64)])
    Checkpoint = C("Checkpoint", [("epoch", U64), ("root", R)])
    Header = C("BeaconBlockHeader", [
        ("slot", U64), ("proposer_index", U64), ("parent_root", R),
        ("state_root", R), ("body_root", R)])
    Eth1Data = C("Eth1Data", [("deposit_root", R), ("deposit_count", U64),
                              ("block_hash", R)])
    Validator = C("Validator", [
        ("pubkey", B48), ("withdrawal_credentials", R),
        ("effective_balance", U64), ("slashed", B),
        ("activation_eligibility_epoch", U64), ("activation_epoch", U64),
        ("exit_epoch", U64), ("withdrawable_epoch", U64)])
    SyncCommittee = C("SyncCommittee", [
        ("pubkeys", S.Vector(B48, p["SYNC_COMMITTEE_SIZE"])),
        ("aggregate_pubkey", B48)])
    payload_head = [
        ("parent_hash", R), ("fee_recipient", S.ByteVector(20)),
        ("state_root", R), ("receipts_root", R),
        ("logs_bloom", S.ByteVector(p["BYTES_PER_LOGS_BLOOM"])),
        ("prev_randao", R), ("block_number", U64), ("gas_limit", U64),
        ("gas_used", U64), ("timestamp", U64),
        ("extra_data", S.ByteList(p["MAX_EXTRA_DATA_BYTES"])),
        ("base_fee_per_gas", U256), ("block_hash", R)]
    PayloadHeader = C("ExecutionPayloadHeader", payload_head + [
        ("transactions_root", R), ("withdrawals_root", R),
        ("blob_gas_used", U64), ("excess_blob_gas", U64)])
    Withdrawal = C("Withdrawal", [
        ("index", U64), ("validator_index", U64),
        ("address", S.ByteVector(20)), ("amount", U64)])
    Payload = C("ExecutionPayload", payload_head + [
        ("transactions", S.List(S.ByteList(p["MAX_BYTES_PER_TRANSACTION"]),
                                p["MAX_TRANSACTIONS_PER_PAYLOAD"])),
        ("withdrawals", S.List(Withdrawal, p["MAX_WITHDRAWALS_PER_PAYLOAD"])),
        ("blob_gas_used", U64), ("excess_blob_gas", U64)])
    Summary = C("HistoricalSummary", [("block_summary_root", R),
                                      ("state_summary_root", R)])
    registry = p["VALIDATOR_REGISTRY_LIMIT"]
    State = C("BeaconState", [
        ("genesis_time", U64), ("genesis_validators_root", R),
        ("slot", U64), ("fork", Fork), ("latest_block_header", Header),
        ("block_roots", S.Vector(R, p["SLOTS_PER_HISTORICAL_ROOT"])),
        ("state_roots", S.Vector(R, p["SLOTS_PER_HISTORICAL_ROOT"])),
        ("historical_roots", S.List(R, p["HISTORICAL_ROOTS_LIMIT"])),
        ("eth1_data", Eth1Data),
        ("eth1_data_votes", S.List(Eth1Data, p["EPOCHS_PER_ETH1_VOTING_PERIOD"]
                                   * p["SLOTS_PER_EPOCH"])),
        ("eth1_deposit_index", U64),
        ("validators", S.Records(Validator, registry)),
        ("balances", S.List(U64, registry)),
        ("randao_mixes", S.Vector(R, p["EPOCHS_PER_HISTORICAL_VECTOR"])),
        ("slashings", S.Vector(U64, p["EPOCHS_PER_SLASHINGS_VECTOR"])),
        ("previous_epoch_participation", S.List(U8, registry)),
        ("current_epoch_participation", S.List(U8, registry)),
        ("justification_bits", S.Bitvector(4)),
        ("previous_justified_checkpoint", Checkpoint),
        ("current_justified_checkpoint", Checkpoint),
        ("finalized_checkpoint", Checkpoint),
        ("inactivity_scores", S.List(U64, registry)),
        ("current_sync_committee", SyncCommittee),
        ("next_sync_committee", SyncCommittee),
        ("latest_execution_payload_header", PayloadHeader),
        ("next_withdrawal_index", U64),
        ("next_withdrawal_validator_index", U64),
        ("historical_summaries", S.List(Summary, p["HISTORICAL_ROOTS_LIMIT"])),
    ])
    AttestationData = C("AttestationData", [
        ("slot", U64), ("index", U64), ("beacon_block_root", R),
        ("source", Checkpoint), ("target", Checkpoint)])
    Attestation = C("Attestation", [
        ("aggregation_bits", S.Bitlist(p["MAX_VALIDATORS_PER_COMMITTEE"])),
        ("data", AttestationData), ("signature", B96)])
    Body = C("BeaconBlockBody", [
        ("randao_reveal", B96), ("eth1_data", Eth1Data), ("graffiti", R),
        ("proposer_slashings", S.EmptyList(p["MAX_PROPOSER_SLASHINGS"])),
        ("attester_slashings", S.EmptyList(p["MAX_ATTESTER_SLASHINGS"])),
        ("attestations", S.List(Attestation, p["MAX_ATTESTATIONS"])),
        ("deposits", S.EmptyList(p["MAX_DEPOSITS"])),
        ("voluntary_exits", S.EmptyList(p["MAX_VOLUNTARY_EXITS"])),
        ("sync_aggregate", C("SyncAggregate", [
            ("sync_committee_bits", S.Bitvector(p["SYNC_COMMITTEE_SIZE"])),
            ("sync_committee_signature", B96)])),
        ("execution_payload", Payload),
        ("bls_to_execution_changes",
         S.EmptyList(p["MAX_BLS_TO_EXECUTION_CHANGES"])),
        ("blob_kzg_commitments",
         S.EmptyList(p["MAX_BLOB_COMMITMENTS_PER_BLOCK"])),
    ])
    Block = C("BeaconBlock", [
        ("slot", U64), ("proposer_index", U64), ("parent_root", R),
        ("state_root", R), ("body", Body)])
    Signed = C("SignedBeaconBlock", [("message", Block), ("signature", B96)])
    return {"BeaconState": State, "SignedBeaconBlock": Signed,
            "BeaconBlockHeader": Header, "BeaconBlockBody": Body,
            "ExecutionPayload": Payload, "Validator": Validator}


# ---------------------------------------------------------------------------
# Shuffling
# ---------------------------------------------------------------------------

def _round_pivot(seed, r, n):
    return int.from_bytes(sha256(seed + bytes([r]))[:8], "little") % n


def shuffled_index(index, n, seed, rounds) -> int:
    """compute_shuffled_index (phase0 beacon-chain.md)."""
    assert index < n
    for r in range(rounds):
        pivot = _round_pivot(seed, r, n)
        flip = (pivot + n - index) % n
        position = max(index, flip)
        source = sha256(seed + bytes([r]) + (position // 256).to_bytes(4,
                                                                    "little"))
        if (source[(position % 256) // 8] >> (position % 8)) % 2:
            index = flip
    return index


def shuffle_permutation(n, seed, rounds):
    """``perm[i] == shuffled_index(i, n, seed, rounds)`` for all ``i``.

    Round ``r`` is an involution ``f_r`` of positions.  Carrying the list
    ``at[x]`` (which index sits at position ``x``) through the rounds
    gives ``at = F^-1`` for ``F = f_last o ... o f_0``, and ``perm`` is
    its inverse."""
    import numpy as np
    x = np.arange(n, dtype=np.int64)
    at = x.copy()
    for r in range(rounds):
        pivot = _round_pivot(seed, r, n)
        src = b"".join(sha256(seed + bytes([r]) + j.to_bytes(4, "little"))
                       for j in range((n + 255) // 256))
        bits = np.unpackbits(np.frombuffer(src, dtype=np.uint8),
                             bitorder="little")
        flip = (pivot + n - x) % n
        f = np.where(bits[np.maximum(x, flip)] == 1, flip, x)
        at = at[f]
    perm = np.empty(n, dtype=np.int64)
    perm[at] = x
    return perm


# ---------------------------------------------------------------------------
# The state transition
# ---------------------------------------------------------------------------

class Registry:
    """Columns of a validator registry that no block of the cell
    changes, read once from its records."""

    FMT = "<48s32sQ?QQQQ"

    def __init__(self, records):
        cols = list(zip(*struct.iter_unpack(self.FMT, records.raw))) \
            if len(records) else [()] * 8
        (self.pubkey, self.credentials, self.effective_balance, self.slashed,
         _, self.activation_epoch, self.exit_epoch,
         self.withdrawable_epoch) = cols
        self.first_index = {}
        for i, pk in enumerate(self.pubkey):
            self.first_index.setdefault(pk, i)
        self.active, self.active_balance = {}, {}

    def active_indices(self, epoch):
        if epoch not in self.active:
            self.active[epoch] = [
                i for i, (a, e) in enumerate(zip(self.activation_epoch,
                                                 self.exit_epoch))
                if a <= epoch < e]
        return self.active[epoch]

    def total_active_balance(self, epoch, increment):
        if epoch not in self.active_balance:
            eb = self.effective_balance
            self.active_balance[epoch] = max(increment, sum(
                eb[i] for i in self.active_indices(epoch)))
        return self.active_balance[epoch]


class Deneb:
    def __init__(self, preset, omit_proposer_reward=False):
        self.p = load_params(preset)
        self.t = types(self.p)
        self.omit_proposer_reward = omit_proposer_reward
        self._registry = {}         # records identity -> Registry
        self._shuffles = {}         # (seed, n) -> shuffled active list

    # -- SSZ ----------------------------------------------------------------

    def decode_state(self, data):
        return self.t["BeaconState"].decode(data)

    def decode_block(self, data):
        return self.t["SignedBeaconBlock"].decode(data)

    def state_root(self, state) -> bytes:
        return self.t["BeaconState"].root(state)

    def header_root(self, header) -> bytes:
        return self.t["BeaconBlockHeader"].root(header)

    # -- accessors ----------------------------------------------------------

    def registry(self, state) -> Registry:
        recs = state["validators"]
        reg = self._registry.get(id(recs))
        if reg is None or reg.records is not recs:
            reg = Registry(recs)
            reg.records = recs
            self._registry[id(recs)] = reg
        return reg

    def epoch_of(self, slot):
        return slot // self.p["SLOTS_PER_EPOCH"]

    def current_epoch(self, state):
        return self.epoch_of(state["slot"])

    def previous_epoch(self, state):
        return max(self.current_epoch(state) - 1, 0)

    def randao_mix(self, state, epoch):
        return state["randao_mixes"][epoch % self.p["EPOCHS_PER_HISTORICAL_VECTOR"]]

    def seed(self, state, epoch, domain):
        mix = self.randao_mix(state, epoch + self.p["EPOCHS_PER_HISTORICAL_VECTOR"]
                              - self.p["MIN_SEED_LOOKAHEAD"] - 1)
        return sha256(domain + u64(epoch) + mix)

    def block_root_at_slot(self, state, slot):
        assert slot < state["slot"] <= slot + self.p["SLOTS_PER_HISTORICAL_ROOT"]
        return state["block_roots"][slot % self.p["SLOTS_PER_HISTORICAL_ROOT"]]

    def committees_per_slot(self, state, epoch):
        n = len(self.registry(state).active_indices(epoch))
        return max(1, min(self.p["MAX_COMMITTEES_PER_SLOT"],
                          n // self.p["SLOTS_PER_EPOCH"]
                          // self.p["TARGET_COMMITTEE_SIZE"]))

    def committee(self, state, slot, index):
        epoch = self.epoch_of(slot)
        active = self.registry(state).active_indices(epoch)
        seed = self.seed(state, epoch, DOMAIN_BEACON_ATTESTER)
        key = (seed, id(active))
        shuffled = self._shuffles.get(key)
        if shuffled is None:
            perm = shuffle_permutation(len(active), seed,
                                       self.p["SHUFFLE_ROUND_COUNT"])
            shuffled = self._shuffles[key] = [active[i] for i in perm.tolist()]
        per_slot = self.committees_per_slot(state, epoch)
        i = (slot % self.p["SLOTS_PER_EPOCH"]) * per_slot + index
        count = per_slot * self.p["SLOTS_PER_EPOCH"]
        n = len(shuffled)
        return shuffled[n * i // count:n * (i + 1) // count]

    def proposer_index(self, state):
        reg = self.registry(state)
        epoch = self.current_epoch(state)
        seed = sha256(self.seed(state, epoch, DOMAIN_BEACON_PROPOSER)
                      + u64(state["slot"]))
        indices = reg.active_indices(epoch)
        total = len(indices)
        i = 0
        while True:
            candidate = indices[shuffled_index(
                i % total, total, seed, self.p["SHUFFLE_ROUND_COUNT"])]
            random_byte = sha256(seed + u64(i // 32))[i % 32]
            if reg.effective_balance[candidate] * MAX_RANDOM_BYTE >= \
                    self.p["MAX_EFFECTIVE_BALANCE"] * random_byte:
                return candidate
            i += 1

    def total_active_balance(self, state):
        return self.registry(state).total_active_balance(
            self.current_epoch(state), self.p["EFFECTIVE_BALANCE_INCREMENT"])

    def base_reward_per_increment(self, state):
        return (self.p["EFFECTIVE_BALANCE_INCREMENT"] * self.p["BASE_REWARD_FACTOR"]
                // math.isqrt(self.total_active_balance(state)))

    @staticmethod
    def increase_balance(state, index, delta):
        state["balances"][index] += delta

    @staticmethod
    def decrease_balance(state, index, delta):
        b = state["balances"]
        b[index] = 0 if delta > b[index] else b[index] - delta

    # -- slots ---------------------------------------------------------------

    def process_slots(self, state, slot):
        assert state["slot"] < slot
        while state["slot"] < slot:
            shr = self.p["SLOTS_PER_HISTORICAL_ROOT"]
            assert (state["slot"] + 1) % self.p["SLOTS_PER_EPOCH"] != 0, \
                "the reference does not process an epoch boundary"
            previous = self.state_root(state)
            state["state_roots"][state["slot"] % shr] = previous
            header = state["latest_block_header"]
            if header["state_root"] == b"\x00" * 32:
                header["state_root"] = previous
            state["block_roots"][state["slot"] % shr] = self.header_root(header)
            state["slot"] += 1

    # -- block ---------------------------------------------------------------

    def state_transition(self, state, signed_block):
        block = signed_block["message"]
        self.process_slots(state, block["slot"])
        self.process_block(state, block)
        assert block["state_root"] == self.state_root(state), \
            "post-state root differs from the block's"

    def process_block(self, state, block):
        body = block["body"]
        self.process_block_header(state, block)
        self.process_withdrawals(state, body["execution_payload"])
        self.process_execution_payload(state, body)
        self.process_randao(state, body)
        self.process_eth1_data(state, body)
        self.process_operations(state, body)
        self.process_sync_aggregate(state, body["sync_aggregate"])

    def process_block_header(self, state, block):
        assert block["slot"] == state["slot"]
        assert block["slot"] > state["latest_block_header"]["slot"]
        assert block["proposer_index"] == self.proposer_index(state)
        assert block["parent_root"] == self.header_root(
            state["latest_block_header"])
        state["latest_block_header"] = {
            "slot": block["slot"], "proposer_index": block["proposer_index"],
            "parent_root": block["parent_root"], "state_root": b"\x00" * 32,
            "body_root": self.t["BeaconBlockBody"].root(block["body"])}
        assert not self.registry(state).slashed[block["proposer_index"]]

    def expected_withdrawals(self, state):
        reg = self.registry(state)
        epoch = self.current_epoch(state)
        max_eb = self.p["MAX_EFFECTIVE_BALANCE"]
        index = state["next_withdrawal_index"]
        v = state["next_withdrawal_validator_index"]
        n = len(state["validators"])
        out = []
        for _ in range(min(n, self.p["MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP"])):
            cred = reg.credentials[v]
            balance = state["balances"][v]
            eth1 = cred[:1] == ETH1_ADDRESS_WITHDRAWAL_PREFIX
            amount = None
            if eth1 and reg.withdrawable_epoch[v] <= epoch and balance > 0:
                amount = balance
            elif eth1 and reg.effective_balance[v] == max_eb \
                    and balance > max_eb:
                amount = balance - max_eb
            if amount is not None:
                out.append({"index": index, "validator_index": v,
                            "address": cred[12:], "amount": amount})
                index += 1
            if len(out) == self.p["MAX_WITHDRAWALS_PER_PAYLOAD"]:
                break
            v = (v + 1) % n
        return out

    def process_withdrawals(self, state, payload):
        expected = self.expected_withdrawals(state)
        assert payload["withdrawals"] == expected
        for w in expected:
            self.decrease_balance(state, w["validator_index"], w["amount"])
        n = len(state["validators"])
        if expected:
            state["next_withdrawal_index"] = expected[-1]["index"] + 1
        if len(expected) == self.p["MAX_WITHDRAWALS_PER_PAYLOAD"]:
            state["next_withdrawal_validator_index"] = \
                (expected[-1]["validator_index"] + 1) % n
        else:
            state["next_withdrawal_validator_index"] = (
                state["next_withdrawal_validator_index"]
                + self.p["MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP"]) % n

    def process_execution_payload(self, state, body):
        payload = body["execution_payload"]
        header = state["latest_execution_payload_header"]
        assert header["block_hash"] != b"\x00" * 32, "not post-merge"
        assert payload["parent_hash"] == header["block_hash"]
        assert payload["prev_randao"] == self.randao_mix(
            state, self.current_epoch(state))
        assert payload["timestamp"] == state["genesis_time"] \
            + state["slot"] * self.p["SECONDS_PER_SLOT"]
        assert len(body["blob_kzg_commitments"]) <= self.p["MAX_BLOBS_PER_BLOCK"]
        P = self.t["ExecutionPayload"]
        fields = dict(P.fields)
        new = {k: payload[k] for k, _ in P.fields
               if k not in ("transactions", "withdrawals")}
        new["transactions_root"] = fields["transactions"].root(
            payload["transactions"])
        new["withdrawals_root"] = fields["withdrawals"].root(
            payload["withdrawals"])
        state["latest_execution_payload_header"] = new

    def process_randao(self, state, body):
        epoch = self.current_epoch(state)
        mix = bytes(a ^ b for a, b in zip(self.randao_mix(state, epoch),
                                          sha256(body["randao_reveal"])))
        state["randao_mixes"][epoch % self.p["EPOCHS_PER_HISTORICAL_VECTOR"]] = mix

    def process_eth1_data(self, state, body):
        state["eth1_data_votes"].append(body["eth1_data"])
        if state["eth1_data_votes"].count(body["eth1_data"]) * 2 > \
                self.p["EPOCHS_PER_ETH1_VOTING_PERIOD"] * self.p["SLOTS_PER_EPOCH"]:
            state["eth1_data"] = body["eth1_data"]

    def process_operations(self, state, body):
        # decoding asserted the lists this reference does not process
        # are empty; deposits must be too
        eth1 = state["eth1_data"]
        assert min(self.p["MAX_DEPOSITS"], eth1["deposit_count"]
                   - state["eth1_deposit_index"]) == 0
        for attestation in body["attestations"]:
            self.process_attestation(state, attestation)

    def flag_indices(self, state, data, inclusion_delay):
        if data["target"]["epoch"] == self.current_epoch(state):
            justified = state["current_justified_checkpoint"]
        else:
            justified = state["previous_justified_checkpoint"]
        source = data["source"] == justified
        target = source and data["target"]["root"] == self.block_root_at_slot(
            state, data["target"]["epoch"] * self.p["SLOTS_PER_EPOCH"])
        head = target and data["beacon_block_root"] == \
            self.block_root_at_slot(state, data["slot"])
        assert source
        flags = []
        if inclusion_delay <= math.isqrt(self.p["SLOTS_PER_EPOCH"]):
            flags.append(TIMELY_SOURCE)
        if target:
            flags.append(TIMELY_TARGET)
        if head and inclusion_delay == self.p["MIN_ATTESTATION_INCLUSION_DELAY"]:
            flags.append(TIMELY_HEAD)
        return flags

    def process_attestation(self, state, attestation):
        data = attestation["data"]
        epoch = data["target"]["epoch"]
        assert epoch in (self.previous_epoch(state), self.current_epoch(state))
        assert epoch == self.epoch_of(data["slot"])
        assert data["slot"] + self.p["MIN_ATTESTATION_INCLUSION_DELAY"] \
            <= state["slot"]
        assert data["index"] < self.committees_per_slot(state, epoch)
        committee = self.committee(state, data["slot"], data["index"])
        bits = attestation["aggregation_bits"]
        assert len(bits) == len(committee)
        flags = self.flag_indices(state, data, state["slot"] - data["slot"])
        attesting = sorted({v for v, b in zip(committee, bits) if b})
        assert attesting, "an attestation with no attester"
        if epoch == self.current_epoch(state):
            participation = state["current_epoch_participation"]
        else:
            participation = state["previous_epoch_participation"]
        reg = self.registry(state)
        per_increment = self.base_reward_per_increment(state)
        increment = self.p["EFFECTIVE_BALANCE_INCREMENT"]
        numerator = 0
        for v in attesting:
            for flag in flags:
                if not participation[v] >> flag & 1:
                    participation[v] |= 1 << flag
                    numerator += (reg.effective_balance[v] // increment
                                  * per_increment * FLAG_WEIGHTS[flag])
        denominator = (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT) \
            * WEIGHT_DENOMINATOR // PROPOSER_WEIGHT
        if not self.omit_proposer_reward:
            self.increase_balance(state, self.proposer_index(state),
                                  numerator // denominator)

    def process_sync_aggregate(self, state, aggregate):
        reg = self.registry(state)
        increment = self.p["EFFECTIVE_BALANCE_INCREMENT"]
        total_increments = self.total_active_balance(state) // increment
        total_base_rewards = self.base_reward_per_increment(state) \
            * total_increments
        max_participant = total_base_rewards * SYNC_REWARD_WEIGHT \
            // WEIGHT_DENOMINATOR // self.p["SLOTS_PER_EPOCH"]
        participant_reward = max_participant // self.p["SYNC_COMMITTEE_SIZE"]
        proposer_reward = participant_reward * PROPOSER_WEIGHT \
            // (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT)
        proposer = self.proposer_index(state)
        members = [reg.first_index[pk]
                   for pk in state["current_sync_committee"]["pubkeys"]]
        for v, bit in zip(members, aggregate["sync_committee_bits"]):
            if bit:
                self.increase_balance(state, v, participant_reward)
                self.increase_balance(state, proposer, proposer_reward)
            else:
                self.decrease_balance(state, v, participant_reward)
