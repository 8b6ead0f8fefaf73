"""CPU rehearsal of the replay cell: the generator, the system against
the plain deneb reference at the mainnet preset (2^14 validators), the
window on the program's device BLS path (its numpy mirror), and the
cell's metric readers.  The minimal-preset comparison and the planted
faults are tier-1 tests (``tests/test_replay_reference.py``)."""
import importlib.util
import os
import types

import pytest

import tiny

D = tiny.driver("replay")
NO_TRACE = tiny.NoTrace()
SEED = 2 ** 31 + 2525
MAINNET_14 = dict(active_validators=2 ** 14, transactions_per_payload=20)
MINIMAL = dict(preset="minimal", active_validators=256, distinct_keys=64,
               transactions_per_payload=5)


def correct(compared):
    return all(limit is None or value <= limit for _, value, limit in compared)


@pytest.fixture
def native():
    from consensus_specs_tpu.utils import bls
    was = bls.bls_active
    bls.bls_active = True
    bls.use_native()
    yield bls
    bls.bls_active = was


@pytest.fixture(scope="module")
def mainnet_work():
    cfg = tiny.config("replay-deneb-mainnet-1m", **MAINNET_14)
    return D.build(cfg, tiny.mix("replay.full", blocks_per_pass=2), SEED)


def test_generation_is_seeded():
    cfg = tiny.config("replay-deneb-mainnet-1m", **MINIMAL)
    mix = tiny.mix("replay.full", blocks_per_pass=2)
    a, b = D.build(cfg, mix, SEED), D.build(cfg, mix, SEED)
    c = D.build(cfg, mix, SEED + 1)
    assert a.blocks == b.blocks and a.tampered == b.tampered
    assert a.blocks[0] != c.blocks[0]


def test_mainnet_blocks_match_reference(mainnet_work, native):
    work = mainnet_work
    spec = work.spec
    block = D.decode_block(spec, work.blocks[1]).message
    # 4 committees of 128 a slot at 2^14: the two slots' 8 aggregates
    assert len(block.body.attestations) == 8
    assert {len(a.aggregation_bits) for a in block.body.attestations} == {128}
    assert len(block.body.execution_payload.withdrawals) == 16
    ops = D.window(work, 1e9, NO_TRACE, max_ops=4)[1]
    assert all(op.ok and not op.failed for op in ops), [op.error for op in ops]
    want = D.reference_roots(work)
    assert [op.root for op in ops] == want * 2
    work.tampered_ok = D.tampered_accepted(work)
    assert work.tampered_ok is False
    assert correct(D.check(work, ops))
    compared = dict((n, v) for n, v, _ in D.control_check(work))
    assert compared["outputs_wrong"] == len(work.blocks) + 1


def test_window_on_the_device_path():
    """``warm`` and the window through ``use_fastest`` -> ``jax`` (the
    numpy mirror of the limb kernels on the CPU): one RLC pairing a
    block, no fallback, roots equal to the reference's."""
    from consensus_specs_tpu.utils import bls
    cfg = tiny.config("replay-deneb-mainnet-1m", **MINIMAL)
    work = D.build(cfg, tiny.mix("replay.full", blocks_per_pass=2), SEED)
    with tiny.patched(bls, "use_fastest", bls.use_jax):
        D.warm(work)
        ops = D.window(work, 1e9, NO_TRACE, max_ops=2)[1]
        compared = D.check(work, ops)
    assert all(op.ok and not op.failed for op in ops), [op.error for op in ops]
    assert all(op.counters["bls.pairings"] == 1 for op in ops)
    assert correct(compared)


def _reader(name):
    path = os.path.join(tiny.PERFBENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _op(spans, counters=None, block=1):
    return types.SimpleNamespace(spans=spans, counters=counters or {},
                                 block=block)


def _run(ops, blocks=8):
    return types.SimpleNamespace(
        ops=ops, work=types.SimpleNamespace(blocks=[b""] * blocks))


def test_span_readers():
    st = "st.state_transition"
    flush = st + "/st.process_block/bls.batch.flush"
    ops = [
        _op({st: 2.0, flush: 1.25, flush + "/bls.batch.flush": 0.5,
             st + "/st.process_block/st.block.attestations/st.committees": 0.1,
             st + "/st.process_slots/st.process_slot/st.state_root": 0.2,
             st + "/st.state_root": 0.3, "st.decode": 0.01}),
        _op({st: 1.0, flush: 0.75}),
    ]
    run = _run(ops)
    assert _reader("st_host_ms.replay")(run) == pytest.approx(500.0)
    assert _reader("bls_flush_ms.replay")(run) == pytest.approx(1000.0)
    assert _reader("st_committees_ms.replay")(run) == pytest.approx(50.0)
    assert _reader("state_root_ms.replay")(run) == pytest.approx(250.0)
    # a program without the spans: nothing to read
    bare = _run([_op({"bls.batch.flush": 1.0})])
    for name in ("st_host_ms.replay", "bls_flush_ms.replay",
                 "st_committees_ms.replay", "state_root_ms.replay"):
        assert _reader(name)(bare) is None


def test_span_readers_weigh_a_traced_window_as_a_pass():
    """A traced window holds a pass's first block and one steady block:
    the first counts once in a pass of 8, the steady one 7 times."""
    st = "st.state_transition"
    run = _run([_op({st: 9.0}, block=0), _op({st: 1.0}, block=1)])
    assert _reader("st_host_ms.replay")(run) == pytest.approx(
        1e3 * (9.0 + 7 * 1.0) / 8)
    whole = _run([_op({st: 9.0}, block=0)]
                 + [_op({st: 1.0}, block=k) for k in range(1, 8)])
    assert _reader("st_host_ms.replay")(whole) == pytest.approx(2000.0)


def test_committee_hit_reader():
    hit = "st.committee_cache{result=hit}"
    miss = "st.committee_cache{result=miss}"
    run = _run([_op({}, {hit: 383, miss: 1}, block=0),
                _op({}, {hit: 384}, block=1)])
    assert _reader("committee_hit.replay")(run) == pytest.approx(
        100 * (383 + 7 * 384) / (8 * 384))
    assert _reader("committee_hit.replay")(_run([_op({})])) is None
