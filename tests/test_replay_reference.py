"""The block-import path against the benchmark's plain reference
(``perfbench/reference/deneb.py``, which imports nothing of the
program): minimal-preset deneb blocks from the replay cell's generator,
with attestations, a sync aggregate and withdrawals, imported through
``state_transition`` and compared root by root; and the faults the
replay cell's output check has to catch, planted under the path it
times."""
import contextlib
import os
import sys

import pytest

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)

from drivers import replay as D  # noqa: E402
from consensus_specs_tpu.utils import bls  # noqa: E402

SEED = 2 ** 32 + 25
CFG = {
    "fork": "deneb", "preset": "minimal", "active_validators": 512,
    "distinct_keys": 64, "transactions_per_payload": 5,
    "transaction_bytes": [100, 1000],
}
MIX = {"driver": "replay", "blocks_per_pass": 4,
       "first_block_limit_s": 45, "block_limit_s": 15}


class NoTrace:
    def around(self, i, name):
        return contextlib.nullcontext()


@contextlib.contextmanager
def patched(obj, attr, value):
    """``obj.attr`` is ``value`` inside the block; afterwards it is
    restored, or removed where ``obj`` had only inherited it, so no fork
    class is left with a method of its own that its markdown lacks."""
    own = vars(obj)
    had, orig = attr in own, own.get(attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        if had:
            setattr(obj, attr, orig)
        else:
            delattr(obj, attr)


@pytest.fixture
def native_bls():
    from consensus_specs_tpu.ops import native_bls as native
    assert native.available()
    was, name = bls.bls_active, bls.backend_name()
    bls.bls_active = True
    bls.use_native()
    yield bls
    bls.bls_active = was
    getattr(bls, "use_" + name)()


@pytest.fixture(scope="module")
def work():
    return D.build(CFG, MIX, SEED)


def one_pass(work):
    """Every block of a pass, imported as the window does: (ok, root)."""
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    state = D.fresh_state(work)
    out = []
    for data in work.blocks:
        ok, error = D._import(work, state, data)
        out.append((ok, bytes(hash_tree_root(state)) if ok else error))
    return out


def correct(compared):
    return all(value <= limit for _, value, limit in compared)


def run_check(work, ops):
    """The output check as a run makes it: the warm-up's import of the
    tampered block, then the check."""
    work.tampered_ok = D.tampered_accepted(work)
    return D.check(work, ops)


def test_blocks_carry_the_cell_operations(work):
    spec = work.spec
    for data in work.blocks:
        block = D.decode_block(spec, data).message
        body = block.body
        # every committee of the two slots before the block
        per_slot = spec.get_committee_count_per_slot(
            work.pre, spec.get_current_epoch(work.pre))
        assert len(body.attestations) == 2 * per_slot
        assert all(all(a.aggregation_bits) for a in body.attestations)
        assert all(body.sync_aggregate.sync_committee_bits)
        assert len(body.execution_payload.withdrawals) == \
            spec.MAX_WITHDRAWALS_PER_PAYLOAD
        assert len(body.execution_payload.transactions) == 5


def test_program_matches_reference_on_minimal_blocks(work, native_bls):
    got = one_pass(work)
    assert all(ok for ok, _ in got), got
    want = D.reference_roots(work)
    assert [root for _, root in got] == want
    assert len(set(want)) == len(want)
    # the pre-state round-trips through the reference's own SSZ
    from consensus_specs_tpu.utils.ssz import hash_tree_root, serialize
    ref = D.REF.Deneb("minimal")
    assert ref.state_root(ref.decode_state(serialize(work.pre))) == \
        bytes(hash_tree_root(work.pre))


def test_later_passes_match_the_first(work, native_bls):
    assert one_pass(work) == one_pass(work)


def test_tampered_block_is_refused(work, native_bls):
    assert D.tampered_accepted(work) is False


def _window(work, passes=1):
    return D.window(work, 1e9, NoTrace(),
                    max_ops=passes * len(work.blocks))[1]


def test_sound_window_is_correct(work, native_bls):
    ops = _window(work, passes=3)
    assert [op.block for op in ops] == list(range(len(work.blocks))) * 3
    assert all(op.ok and not op.failed for op in ops), \
        [op.error for op in ops]
    assert [op.counters.get("st.shuffles", 0) for op in ops] == \
        [1, 0, 0, 0] * 3
    assert correct(run_check(work, ops))


def test_window_closes_with_a_pass(work, native_bls):
    """Past its length the window runs on to the end of the pass: the
    pass's first block pays the epoch's shuffle, so the rate depends on
    whole passes only."""
    ops = D.window(work, 3.0, NoTrace())[1]
    assert ops[-1].t1 - ops[0].t0 >= 3.0
    assert [op.block for op in ops] == \
        list(range(len(work.blocks))) * (len(ops) // len(work.blocks))


def test_control_reads_wrong(work):
    compared = dict((n, v) for n, v, _ in D.control_check(work))
    # every block's root, and the tampered block accepted
    assert compared["outputs_wrong"] == len(work.blocks) + 1


@contextlib.contextmanager
def no_self_check(spec):
    """``state_transition`` without its own post-state root check, so a
    wrong post-state is left to the output check to find."""
    cls = type(spec)
    orig = cls.state_transition

    def unchecked(self, state, signed_block, validate_result=True):
        return orig(self, state, signed_block, validate_result=False)
    with patched(cls, "state_transition", unchecked):
        yield


def test_fault_participation_flag_skipped(work, native_bls):
    from consensus_specs_tpu.forks.altair import TIMELY_HEAD_FLAG_INDEX
    cls = type(work.spec)
    orig = cls.get_attestation_participation_flag_indices

    def no_head(self, state, data, inclusion_delay):
        flags = orig(self, state, data, inclusion_delay)
        return [f for f in flags if f != TIMELY_HEAD_FLAG_INDEX]
    with no_self_check(work.spec), patched(
            cls, "get_attestation_participation_flag_indices", no_head):
        ops = _window(work)
    # the first block is accepted with a wrong post-state: the root
    # comparison finds it (the next block's parent root no longer links)
    assert ops[0].ok and ops[0].root != D.reference_roots(work)[0]
    assert not correct(run_check(work, ops))


def test_fault_withdrawal_skipped(work, native_bls):
    cls = type(work.spec)
    orig = cls.process_withdrawals

    def skip_first(self, state, payload):
        skipped = []
        decrease = self.decrease_balance

        def once(state, index, delta):
            if not skipped:
                skipped.append(index)
                return None
            return decrease(state, index, delta)
        with patched(self, "decrease_balance", once):
            return orig(self, state, payload)
    with no_self_check(work.spec), patched(
            cls, "process_withdrawals", skip_first):
        ops = _window(work)
    # the first block is accepted with a wrong post-state: the root
    # comparison finds it (the next block's parent root no longer links)
    assert ops[0].ok and ops[0].root != D.reference_roots(work)[0]
    assert not correct(run_check(work, ops))


def test_fault_wrong_proposer_reward(work, native_bls):
    from consensus_specs_tpu.forks import altair
    heavier = [w + 1 for w in altair.PARTICIPATION_FLAG_WEIGHTS]
    with no_self_check(work.spec), patched(
            altair, "PARTICIPATION_FLAG_WEIGHTS", heavier):
        ops = _window(work)
    # the first block is accepted with a wrong post-state: the root
    # comparison finds it (the next block's parent root no longer links)
    assert ops[0].ok and ops[0].root != D.reference_roots(work)[0]
    assert not correct(run_check(work, ops))


def test_fault_tampered_block_accepted(work, native_bls):
    with patched(bls.DeferredBatch, "flush", lambda self: True):
        ops = _window(work)
        compared = run_check(work, ops)
    assert all(op.ok for op in ops)
    assert dict((n, v) for n, v, _ in compared)["outputs_wrong"] == 1


def test_slow_generation_stops_with_exit_2():
    slow = dict(MIX, first_block_limit_s=0)
    with pytest.raises(SystemExit) as e:
        D.build(CFG, slow, SEED)
    assert e.value.code == 2
