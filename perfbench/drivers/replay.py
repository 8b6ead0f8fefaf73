"""Driver for block-import traffic: consecutive full deneb blocks decoded
from SSZ bytes and imported through ``spec.state_transition`` (the
signature batch on ``bls.use_fastest()``), in a closed loop over
``blocks_per_pass`` blocks, the state reset to the same pre-state at the
start of every pass.

Generation (from the configuration, the mix and ``--seed``): a synthetic
deneb state of ``active_validators`` validators at the third slot of an
epoch.  Validator ``v`` holds secret key ``(v mod distinct_keys) + 1``
(``test_infra/keys.py``'s keys) and the withdrawal address of that key
under 0x01 credentials; balances are 32 ETH plus a seeded excess.  The
sync committee is ``sync_committee_size`` distinct validators below
``distinct_keys``, so the spec's first-index pubkey lookup finds the
member itself.  Then ``blocks_per_pass`` consecutive blocks, built by
the program with signature checks off and signed with summed secret
keys (one native ``Sign`` a set): each carries one full-bit attestation
for every committee of the two slots before it, a full sync aggregate,
the sweep's withdrawals and seeded opaque transactions.  One seeded block
also gets a tampered twin: one attestation's signature replaced by
another's, re-signed by its proposer, with the post-state root its body
gives, so only the signature is wrong.

Window: each operation decodes one block from its bytes (``st.decode``)
and runs ``state_transition`` on it; the window ends with a pass (see
:func:`window`).  Before each block the verify memo is cleared; at the
start of each pass the state is reset by ``serving.clone.clone_state``
of the pre-state held since set-up, and the program's content-keyed
caches are cleared, so no pass reuses what an earlier pass of the same
blocks computed.

Correctness (``check``): the post-state root after every block of the
first pass and of one seeded later pass, against the plain reference
(``reference/deneb.py``) run from the same pre-state and block bytes;
every block accepted; and the tampered block, imported by the warm-up
on its own pre-state, rejected.
"""
import gc
import hashlib
import sys
import time
from dataclasses import dataclass, field

from reference import deneb as REF
from reference import ssz as REF_SSZ

R_ORDER = 0x73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001
FALLBACK_KEYS = ("fallbacks", "path=fallback")
PAIRINGS = "bls.pairings"
RLC_FLUSHES = "bls.flush{path=rlc}"


def seeded(seed, *tags) -> int:
    """A 64-bit integer from the seed and tags (any int seed works)."""
    h = hashlib.sha256(repr((int(seed),) + tags).encode()).digest()
    return int.from_bytes(h[:8], "little")


@dataclass
class Op:
    index: int                  # position in the window
    block: int                  # position in the pass
    t0: float
    t1: float
    units: int                  # signature sets in the block
    ok: bool                    # state_transition returned
    root: bytes                 # post-state root, None if it raised
    counters: dict              # counter deltas of the op
    failed: bool                # raised, fell back, or not one RLC pairing
    error: str = None
    spans: dict = field(default_factory=dict)


@dataclass
class Work:
    cfg: dict
    mix: dict
    seed: int
    spec: object
    pre: object                 # program state, never mutated
    blocks: list                # SSZ bytes of the signed blocks
    units: list                 # signature sets per block
    tampered: tuple             # (block position, SSZ bytes, its pre-state)
    tampered_ok: bool = None    # state_transition accepted the tampered block
    repeats: int = 0


def _keys(cfg):
    from consensus_specs_tpu.ops import native_bls
    if not native_bls.available():
        raise RuntimeError("native BLS library unavailable for signing")
    return [native_bls.SkToPk(k + 1) for k in range(cfg["distinct_keys"])]


def _key_of(cfg, v) -> int:
    return int(v) % cfg["distinct_keys"] + 1


def synthetic_state(spec, cfg, seed, pubkeys):
    """The pre-state: a deneb registry of ``active_validators`` at the
    third slot of a seeded epoch, every field a block reads seeded."""
    import numpy as np
    from consensus_specs_tpu.ops import native_bls
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    rng = np.random.default_rng(seeded(seed, "replay state"))
    n, keys = cfg["active_validators"], cfg["distinct_keys"]
    spe = int(spec.SLOTS_PER_EPOCH)
    max_eb = int(spec.MAX_EFFECTIVE_BALANCE)
    far = int(spec.FAR_FUTURE_EPOCH)
    epoch = 1000 + int(rng.integers(1000))
    slot = epoch * spe + 2
    addresses = [rng.bytes(20) for _ in range(keys)]
    # one record per key, repeated: the state's list copies each element
    per_key = [spec.Validator(
        pubkey=pubkeys[k],
        withdrawal_credentials=b"\x01" + b"\x00" * 11 + addresses[k],
        effective_balance=max_eb, exit_epoch=far, withdrawable_epoch=far)
        for k in range(keys)]
    validators = [per_key[v % keys] for v in range(n)]
    balances = (max_eb + rng.integers(1, 10 ** 9, n)).tolist()
    previous = rng.choice(np.array([7, 7, 7, 7, 7, 7, 3, 0]), n).tolist()

    def roots(count):
        raw = rng.bytes(32 * count)
        return [raw[32 * i:32 * i + 32] for i in range(count)]

    def committee():
        members = sorted(rng.choice(keys, int(spec.SYNC_COMMITTEE_SIZE),
                                    replace=False).tolist())
        pks = [pubkeys[m] for m in members]
        return spec.SyncCommittee(pubkeys=pks,
                                  aggregate_pubkey=native_bls.AggregatePKs(pks))

    cfgv = spec.config
    genesis_time = 1606824023
    header = spec.ExecutionPayloadHeader(
        parent_hash=rng.bytes(32), fee_recipient=rng.bytes(20),
        state_root=rng.bytes(32), receipts_root=rng.bytes(32),
        logs_bloom=rng.bytes(int(spec.BYTES_PER_LOGS_BLOOM)),
        prev_randao=rng.bytes(32), block_number=19_000_000 + slot % 10 ** 6,
        gas_limit=30_000_000, gas_used=int(rng.integers(10 ** 7, 3 * 10 ** 7)),
        timestamp=genesis_time + (slot - 1) * int(cfgv.SECONDS_PER_SLOT),
        base_fee_per_gas=int(rng.integers(10 ** 9, 10 ** 11)),
        block_hash=rng.bytes(32), transactions_root=rng.bytes(32),
        withdrawals_root=rng.bytes(32))
    eth1_count = 1_000_000 + int(rng.integers(10 ** 5))
    state = spec.BeaconState(
        genesis_time=genesis_time,
        genesis_validators_root=rng.bytes(32),
        slot=slot,
        fork=spec.Fork(previous_version=cfgv.CAPELLA_FORK_VERSION,
                       current_version=cfgv.DENEB_FORK_VERSION,
                       epoch=epoch - 10),
        latest_block_header=spec.BeaconBlockHeader(
            slot=slot, proposer_index=int(rng.integers(n)),
            parent_root=rng.bytes(32), body_root=rng.bytes(32)),
        block_roots=roots(int(spec.SLOTS_PER_HISTORICAL_ROOT)),
        state_roots=roots(int(spec.SLOTS_PER_HISTORICAL_ROOT)),
        eth1_data=spec.Eth1Data(deposit_root=rng.bytes(32),
                                deposit_count=eth1_count,
                                block_hash=rng.bytes(32)),
        eth1_deposit_index=eth1_count,
        validators=validators,
        balances=balances,
        randao_mixes=roots(int(spec.EPOCHS_PER_HISTORICAL_VECTOR)),
        previous_epoch_participation=previous,
        current_epoch_participation=[0] * n,
        justification_bits=[True, True, True, True],
        previous_justified_checkpoint=spec.Checkpoint(
            epoch=epoch - 2, root=rng.bytes(32)),
        current_justified_checkpoint=spec.Checkpoint(
            epoch=epoch - 1, root=rng.bytes(32)),
        finalized_checkpoint=spec.Checkpoint(
            epoch=epoch - 2, root=rng.bytes(32)),
        inactivity_scores=[0] * n,
        current_sync_committee=committee(),
        next_sync_committee=committee(),
        latest_execution_payload_header=header,
        next_withdrawal_index=int(rng.integers(10 ** 8)),
        next_withdrawal_validator_index=int(rng.integers(n)),
    )
    hash_tree_root(state)
    return state


class _Signer:
    """Summed-key signatures, one native ``Sign`` a distinct message."""

    def __init__(self, cfg):
        from consensus_specs_tpu.ops import native_bls
        self.cfg, self.sign, self.memo = cfg, native_bls.Sign, {}

    def __call__(self, members, message) -> bytes:
        sk = sum(_key_of(self.cfg, v) for v in members) % R_ORDER
        key = (sk, bytes(message))
        if key not in self.memo:
            self.memo[key] = self.sign(sk, bytes(message))
        return self.memo[key]


def _attestations(spec, state, slot, sign):
    """One full-bit attestation for every committee of the two slots
    before ``slot`` (``state`` is at ``slot``, before its block)."""
    epoch = spec.get_current_epoch(state)
    target = spec.Checkpoint(epoch=epoch, root=spec.get_block_root(state, epoch))
    domain = spec.get_domain(state, spec.DOMAIN_BEACON_ATTESTER, epoch)
    out = []
    for att_slot in (slot - 1, slot - 2):
        assert spec.compute_epoch_at_slot(att_slot) == epoch
        for c in range(spec.get_committee_count_per_slot(state, epoch)):
            if len(out) == spec.MAX_ATTESTATIONS:
                return out
            data = spec.AttestationData(
                slot=att_slot, index=c,
                beacon_block_root=spec.get_block_root_at_slot(state, att_slot),
                source=state.current_justified_checkpoint, target=target)
            committee = spec.get_beacon_committee(state, att_slot, c)
            out.append(spec.Attestation(
                aggregation_bits=[True] * len(committee), data=data,
                signature=sign(committee, spec.compute_signing_root(
                    data, domain))))
    return out


def _block(spec, cfg, state, sign, rng, key_index):
    """The next block on ``state`` (advanced to its slot), unsigned and
    without its state root; ``state`` is left at the block's slot.
    ``key_index`` maps a pubkey to the validator below ``distinct_keys``
    that holds it."""
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    slot = state.slot + 1
    spec.process_slots(state, slot)
    epoch = spec.get_current_epoch(state)
    proposer = spec.get_beacon_proposer_index(state)
    randao = sign([proposer], spec.compute_signing_root(
        spec.uint64(epoch), spec.get_domain(state, spec.DOMAIN_RANDAO)))
    previous = slot - 1
    sync_root = spec.compute_signing_root(
        spec.get_block_root_at_slot(state, previous),
        spec.get_domain(state, spec.DOMAIN_SYNC_COMMITTEE,
                        spec.compute_epoch_at_slot(previous)))
    sync_members = [key_index[bytes(pk)]
                    for pk in state.current_sync_committee.pubkeys]
    head = state.latest_execution_payload_header
    lo, hi = cfg["transaction_bytes"]
    payload = spec.ExecutionPayload(
        parent_hash=head.block_hash, fee_recipient=rng.bytes(20),
        state_root=rng.bytes(32), receipts_root=rng.bytes(32),
        logs_bloom=rng.bytes(int(spec.BYTES_PER_LOGS_BLOOM)),
        prev_randao=spec.get_randao_mix(state, epoch),
        block_number=head.block_number + 1, gas_limit=head.gas_limit,
        gas_used=int(rng.integers(10 ** 7, 3 * 10 ** 7)),
        timestamp=spec.compute_timestamp_at_slot(state, slot),
        base_fee_per_gas=int(rng.integers(10 ** 9, 10 ** 11)),
        block_hash=rng.bytes(32),
        transactions=[rng.bytes(int(rng.integers(lo, hi + 1)))
                      for _ in range(cfg["transactions_per_payload"])],
        withdrawals=spec.get_expected_withdrawals(state))
    body = spec.BeaconBlockBody(
        randao_reveal=randao, eth1_data=state.eth1_data,
        graffiti=rng.bytes(32),
        attestations=_attestations(spec, state, slot, sign),
        sync_aggregate=spec.SyncAggregate(
            sync_committee_bits=[True] * len(sync_members),
            sync_committee_signature=sign(sync_members, sync_root)),
        execution_payload=payload)
    return spec.BeaconBlock(
        slot=slot, proposer_index=proposer,
        parent_root=hash_tree_root(state.latest_block_header), body=body)


def _signed(spec, state, block, sign):
    from consensus_specs_tpu.utils.ssz import serialize
    sig = sign([block.proposer_index], spec.compute_signing_root(
        block, spec.get_domain(state, spec.DOMAIN_BEACON_PROPOSER)))
    return serialize(spec.SignedBeaconBlock(message=block, signature=sig))


def _guard(k, seconds, mix):
    limit = mix["first_block_limit_s"] if k == 0 else mix["block_limit_s"]
    if seconds > limit:
        print(f"replay: block {k} took {seconds:.1f} s to build, over the "
              f"{limit} s a block may take here (a third of the window, the "
              f"whole window for the first block, which pays the epoch's "
              f"committee shuffle): the program cannot run this cell",
              file=sys.stderr, flush=True)
        raise SystemExit(2)


def build(cfg, mix, seed) -> Work:
    """All inputs of one run, from the seed alone.  The collector is
    paused meanwhile: generation makes millions of objects and no
    reference cycle, and the collector's rescans of the growing heap
    would cost a sixth of it."""
    collecting = gc.isenabled()
    gc.disable()
    try:
        return _build(cfg, mix, seed)
    finally:
        if collecting:
            gc.enable()


def _build(cfg, mix, seed) -> Work:
    import numpy as np
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.serving.clone import clone_state
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    spec = build_spec(cfg["fork"], cfg["preset"])
    pubkeys = _keys(cfg)
    pre = synthetic_state(spec, cfg, seed, pubkeys)
    sign = _Signer(cfg)
    key_index = {pk: v for v, pk in enumerate(pubkeys)}
    rng = np.random.default_rng(seeded(seed, "replay blocks"))
    k_pass = mix["blocks_per_pass"]
    tamper_at = int(seeded(seed, "tampered block") % k_pass)
    was = bls.bls_active
    bls.bls_active = False
    try:
        state = clone_state(pre)
        blocks, units, tampered = [], [], None
        for k in range(k_pass):
            t0 = time.perf_counter()
            if k == tamper_at:
                tampered_pre = clone_state(state)
            block = _block(spec, cfg, state, sign, rng, key_index)
            spec.process_block(state, block)
            block.state_root = hash_tree_root(state)
            _guard(k, time.perf_counter() - t0, mix)
            blocks.append(_signed(spec, state, block, sign))
            units.append(len(block.body.attestations) + 3)
            if k == tamper_at:
                tampered = (k, _tampered(spec, state, block, sign, seed),
                            tampered_pre)
    finally:
        bls.bls_active = was
    return Work(cfg, mix, seed, spec, pre, blocks, units, tampered)


def _tampered(spec, state, block, sign, seed):
    """``block`` with one attestation's signature replaced by another's,
    re-signed, carrying the post-state root of its own body: ``state`` is
    ``block``'s post-state, whose header differs only in the body root."""
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    atts = block.body.attestations
    a = int(seeded(seed, "tampered attestation") % len(atts))
    b = (a + 1 + int(seeded(seed, "donor") % (len(atts) - 1))) % len(atts)
    bad = block.copy()
    bad.body.attestations[a].signature = atts[b].signature
    body_root = state.latest_block_header.body_root
    state.latest_block_header.body_root = hash_tree_root(bad.body)
    bad.state_root = hash_tree_root(state)
    state.latest_block_header.body_root = body_root
    return _signed(spec, state, bad, sign)


def clear_caches(spec):
    """The program's content-keyed caches: what a later pass of the same
    blocks could otherwise reuse."""
    from consensus_specs_tpu.ops import att_prep
    for cache in spec._caches.values():
        cache.clear()
    att_prep._table = {}
    att_prep._prepared_srcs = []


def fresh_state(work):
    """A pass's state: a clone of the pre-state, and empty content-keyed
    caches."""
    from consensus_specs_tpu.serving.clone import clone_state
    clear_caches(work.spec)
    return clone_state(work.pre)


def decode_block(spec, data):
    from consensus_specs_tpu.obs.tracing import span
    from consensus_specs_tpu.utils.ssz import deserialize
    with span("st.decode"):
        return deserialize(spec.SignedBeaconBlock, data)


def _import(work, state, data):
    """Decode and import one block: (ok, error)."""
    from consensus_specs_tpu.utils import bls
    spec = work.spec
    bls.clear_verify_memo()
    signed = decode_block(spec, data)
    try:
        spec.state_transition(state, signed)
        return True, None
    except Exception as e:      # an invalid block raises
        return False, f"{type(e).__name__}: {e}"


def warm(work):
    """The program path the window drives, at the window's shapes:
    ``use_fastest`` must pick the device backend.  The warm-up imports
    the tampered block on its pre-state, kept from generation with the
    generator's caches (the window clears them before every pass): its
    flush runs the RLC programs of every window block (131 sets, the same
    lane bucket) before their combined check fails, then the per-set
    bisect; whether ``state_transition`` accepted it is kept for
    :func:`check`."""
    from consensus_specs_tpu.utils import bls
    bls.bls_active = True
    bls.use_fastest()
    if bls.backend_name() != "jax":
        raise RuntimeError(f"use_fastest picked {bls.backend_name()!r}")
    work.tampered_ok = tampered_accepted(work)


def tampered_accepted(work) -> bool:
    """Import the tampered block on its own pre-state with signatures
    checked: True if ``state_transition`` accepts it."""
    from consensus_specs_tpu.serving.clone import clone_state
    _, data, pre = work.tampered
    return _import(work, clone_state(pre), data)[0]


def _fell_back(key) -> bool:
    return any(k in key for k in FALLBACK_KEYS)


def window(work, seconds, tracer, span_stats=None, max_ops=None):
    """Import blocks back to back until ``seconds`` have passed; the
    window closes at the end of the first pass to finish after that.
    Every pass pays its epoch's shuffle in its first block, several times
    a steady block, so a window of whole passes keeps the rate from
    swinging with where in a pass it would end.  A block that alone took
    over half of ``seconds`` closes it at once once ``seconds`` have
    passed (the traced block, which writes its trace inside it: the
    per-layer readers weigh the blocks they get as a pass, ``span_sum``),
    and so does the ``max_ops``-th block.
    ``tracer.around(i, name)`` brackets op ``i``; ``span_stats``, when
    given, is read around every op."""
    from consensus_specs_tpu.obs import registry
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    ops = []
    k_pass = len(work.blocks)
    state, k = None, 0
    t_open = time.perf_counter()
    i = 0
    while True:
        if k == 0 or state is None:
            state, k = fresh_state(work), 0
        c0 = registry.counter_values()
        s0 = span_stats() if span_stats else None
        t0 = time.perf_counter()
        with tracer.around(i, "bench.block"):
            ok, error = _import(work, state, work.blocks[k])
        t1 = time.perf_counter()
        c1 = registry.counter_values()
        delta = {key: v - c0.get(key, 0) for key, v in c1.items()
                 if v != c0.get(key, 0)}
        fb = {key: v for key, v in delta.items() if _fell_back(key)}
        one_pairing = delta.get(PAIRINGS) == 1 and delta.get(RLC_FLUSHES) == 1
        op = Op(i, k, t0, t1, work.units[k], ok,
                bytes(hash_tree_root(state)) if ok else None, delta,
                not ok or bool(fb) or not one_pairing, error)
        if span_stats:
            s1 = span_stats()
            op.spans = {key: v - s0.get(key, 0.0) for key, v in s1.items()}
        ops.append(op)
        if not ok:
            state = None        # the rest of the pass has no pre-state
        i, k = i + 1, (k + 1) % k_pass
        pass_done = k == 0 or state is None
        if t1 - t_open >= seconds and (pass_done or t1 - t0 > seconds / 2) \
                or len(ops) == max_ops:
            break
    work.repeats = max(0, len(ops) - k_pass)
    return t_open, ops


# ---------------------------------------------------------------------------
# Correctness: post-state roots against the plain reference
# ---------------------------------------------------------------------------

def reference_roots(work, omit_proposer_reward=False):
    """The reference's post-state root after each block of a pass, from
    the pre-state's and the blocks' bytes; None where it refuses one (and
    for every block after it)."""
    from consensus_specs_tpu.utils.ssz import serialize
    ref = REF.Deneb(work.cfg["preset"],
                    omit_proposer_reward=omit_proposer_reward)
    REF_SSZ.clear_memo()
    state = ref.decode_state(serialize(work.pre))
    roots = []
    for data in work.blocks:
        try:
            signed = ref.decode_block(data)
            block = signed["message"]
            ref.process_slots(state, block["slot"])
            ref.process_block(state, block)
            roots.append(ref.state_root(state))
        except Exception:       # the reference refuses the block
            break
    REF_SSZ.clear_memo()
    return roots + [None] * (len(work.blocks) - len(roots))


def checked_passes(work, ops):
    """The first pass and one seeded later complete pass, as lists of
    ops in block order."""
    passes = []
    for op in ops:
        if op.block == 0 or not passes:
            passes.append([])
        passes[-1].append(op)
    later = [p for p in passes[1:] if len(p) == len(work.blocks)]
    if later:
        return passes[:1] + [later[seeded(work.seed, "pass") % len(later)]]
    return passes[:1]


def compare(work, ops, want, tampered_ok):
    """``outputs_wrong``: blocks of the checked passes whose post-state
    root differs from the reference's (or that were refused), blocks
    refused anywhere in the window, and the tampered block accepted."""
    wrong = 0
    checked = set()
    for run in checked_passes(work, ops):
        for op in run:
            checked.add(op.index)
            wrong += op.root is None or op.root != want[op.block]
    wrong += sum(1 for op in ops if not op.ok and op.index not in checked)
    return wrong + bool(tampered_ok)


def check(work, ops):
    """Numbers compared, each with its limit: ``(name, value, limit)``;
    the tampered block was imported by :func:`warm`."""
    want = reference_roots(work)
    return [("outputs_wrong", compare(work, ops, want,
                                      work.tampered_ok is not False), 0)]


def control_check(work):
    """The check's numbers for the control: the reference with the
    attestation proposer reward left out, in the program's place over
    one pass; it checks no signature, so it accepts the tampered block."""
    roots = reference_roots(work, omit_proposer_reward=True)
    ops = [Op(k, k, 0.0, 0.0, work.units[k], r is not None, r, {}, r is None)
           for k, r in enumerate(roots)]
    return [("outputs_wrong", compare(work, ops, reference_roots(work),
                                      True), 0)]
