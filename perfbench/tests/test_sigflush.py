"""CPU rehearsal of the signature-flush generator, window and output
check at a tiny size, with the program's staged BLS pipeline on its
numpy mirror; the control; and the faults the cell can have, planted
under the timed path, each of which must turn ``correct`` false."""
import pytest

import tiny

D = tiny.driver("sigflush")
NO_TRACE = tiny.NoTrace()
SEED = 2 ** 31 + 12345


def correct(compared):
    return all(limit is None or value <= limit for _, value, limit in compared)


@pytest.fixture(scope="module")
def setting():
    cfg = tiny.config("blocksig-mainnet-1m", **tiny.TINY_SIGFLUSH)
    mix = tiny.mix("blocksig.full", pool_flushes=2)
    return cfg, mix


@pytest.fixture(scope="module")
def jax_backend():
    from consensus_specs_tpu.utils import bls
    with tiny.patched(bls, "use_fastest", bls.use_jax):
        yield bls


def run_window(cfg, mix, seed, seconds=0.0):
    work = D.build(cfg, mix, seed)
    D.warm(work)
    _, ops = D.window(work, seconds, NO_TRACE)
    return work, ops


def test_generation_is_seeded(setting):
    cfg, mix = setting
    a, b = D.build(cfg, mix, SEED), D.build(cfg, mix, SEED)
    c = D.build(cfg, mix, SEED + 1)
    assert [f.items for f in a.pool] == [f.items for f in b.pool]
    assert a.pool[0].items != c.pool[0].items
    fl = a.pool[0]
    n = tiny.TINY_SIGFLUSH
    assert len(fl.items) == n["max_attestations"] + 1 + n["singles"]
    assert [len(p) for p, _, _ in fl.items[:n["max_attestations"]]] == \
        [n["committee_width"]] * n["max_attestations"]
    sigs = [s for f in a.pool + [a.warm] for _, _, s in f.items]
    assert len(set(sigs)) == len(sigs)          # no signature repeats


def test_sound_run_is_correct(setting, jax_backend):
    cfg, mix = setting
    work, ops = run_window(cfg, mix, SEED, seconds=0.5)
    assert ops and all(op.ok and not op.failed for op in ops)
    assert all(op.host_s is not None and 0 < op.host_s < op.t1 - op.t0
               for op in ops)
    compared = D.check(work, ops)
    assert [name for name, _, _ in compared] == ["outputs_wrong"]
    assert correct(compared), compared


def test_control_fails(setting):
    """64-bit RLC scalars in place of the configuration's 128: every
    scaled pubkey and the signature sum differ."""
    cfg, mix = setting
    work = D.build(cfg, mix, SEED)
    compared = dict((n, v) for n, v, _ in D.control_check(work))
    per_flush = len(work.pool[0].items) + 1
    assert compared["outputs_wrong"] == per_flush * D.CHECKED_FLUSHES
    # the same control at the stated 128 bits reads nothing
    assert correct(D.control_check(D.build(cfg, mix, SEED), 128))


def test_fault_half_the_batch_left_out(setting, jax_backend):
    from consensus_specs_tpu.ops import bls_rlc
    orig = bls_rlc.combined_check

    def half(items, checks, backend):
        return orig(items[:len(items) // 2], checks, backend)

    cfg, mix = setting
    work = D.build(cfg, mix, SEED + 7)
    D.warm(work)
    with tiny.patched(bls_rlc, "combined_check", half):
        _, ops = D.window(work, 0.0, NO_TRACE)
    assert all(op.ok for op in ops)             # the verdict alone is blind
    assert not correct(D.check(work, ops))


def test_fault_answer_altered_where_produced(setting, jax_backend):
    bls = jax_backend
    orig = bls.DeferredBatch.flush

    def flipped(self):
        return not orig(self)

    cfg, mix = setting
    work = D.build(cfg, mix, SEED + 8)
    D.warm(work)
    with tiny.patched(bls.DeferredBatch, "flush", flipped):
        _, ops = D.window(work, 0.0, NO_TRACE)
    compared = dict((n, v) for n, v, _ in D.check(work, ops))
    assert compared["outputs_wrong"] >= len(ops)


def test_fault_pairing_inputs_altered(setting, jax_backend):
    """A hashed point swapped between two sets where it is produced."""
    from consensus_specs_tpu.ops import bls_jax
    orig = bls_jax._program_htc

    def swapped(u0, u1):
        import jax
        import numpy as np

        def swap(a):
            a = np.array(a)
            a[[0, 1]] = a[[1, 0]]
            return a
        return jax.tree_util.tree_map(swap, orig(u0, u1))

    cfg, mix = setting
    work = D.build(cfg, mix, SEED + 9)
    D.warm(work)
    with tiny.patched(bls_jax, "_program_htc", swapped):
        _, ops = D.window(work, 0.0, NO_TRACE)
    assert not correct(D.check(work, ops))


def test_moved_pair_is_refused_by_a_sound_pairing(setting, jax_backend):
    cfg, mix = setting
    work, ops = run_window(cfg, mix, SEED + 10)
    args = work.captured[0][0]
    assert D.pairing_accepts_moved_pair(work.seed, 0, args) is False


def test_fault_pairing_always_true(setting, jax_backend):
    """A product pairing that answers True whatever its pairs: every set
    is valid, so only the moved pair of the check can see it."""
    from consensus_specs_tpu.ops.jax_bls import pairing as PR

    def always_true(px, py, q, degenerate):
        return True

    cfg, mix = setting
    work = D.build(cfg, mix, SEED + 11)
    D.warm(work)
    with tiny.patched(PR, "staged_product_pairing_check", always_true):
        _, ops = D.window(work, 0.0, NO_TRACE)
        compared = D.check(work, ops)
    assert all(op.ok for op in ops)             # the verdicts alone are blind
    assert not correct(compared)
