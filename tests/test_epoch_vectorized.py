"""Differential equivalence suite for the vectorized epoch engine.

``ops/epoch_kernels.py`` re-expresses the O(validators) epoch loops as
columnar array kernels; its exactness contract is bit-identical
post-state ``hash_tree_root`` against the per-validator spec loops.
This suite enforces that contract per fork and per epoch function over
randomized states seeded with the edge shapes the kernels special-case:
slashed validators (mid-withdrawability, the ``prev+1 == withdrawable``
eligibility boundary, and the ``process_slashings`` target epoch),
exited and exiting validators, ejection candidates at the balance
threshold, activation-queue stamps, finalized-boundary activation
eligibility, hysteresis-straddling balances, zero-participation epochs
and inactivity-leak epochs.

The engine's fallback/commit counters are asserted around every
vectorized run so a silent guard fallback cannot quietly turn these
comparisons into loop-vs-loop tautologies.
"""
from random import Random

import numpy as np
import pytest

from consensus_specs_tpu import faults
from consensus_specs_tpu.forks import build_spec
from consensus_specs_tpu.ops import epoch_kernels as ek
from consensus_specs_tpu.state import arrays as state_arrays
from consensus_specs_tpu.test_infra.attestations import (
    next_epoch_with_attestations)
from consensus_specs_tpu.test_infra.block import next_epoch
from consensus_specs_tpu.test_infra.epoch_processing import (
    get_process_calls, run_epoch_processing_to)
from consensus_specs_tpu.test_infra.genesis import create_genesis_state
from consensus_specs_tpu.test_infra.metrics import counting
from consensus_specs_tpu.utils import bls
from consensus_specs_tpu.utils.ssz import (
    List, hash_tree_root, uint64)

PHASE0_FAMILY = ["phase0", "sharding", "custody_game"]
ALTAIR_FAMILY = ["altair", "bellatrix", "capella", "deneb",
                 "eip6110", "eip7002", "eip7594", "whisk", "eip6914"]

VECTORIZED_FNS = ["process_rewards_and_penalties", "process_registry_updates",
                  "process_slashings", "process_effective_balance_updates"]
ALTAIR_VECTORIZED_FNS = ["process_inactivity_updates"] + VECTORIZED_FNS

N_VALIDATORS = 64


@pytest.fixture(autouse=True)
def _engine_mode_reset():
    """Every test leaves the process-global switch back at auto, and
    runs with signature checks off (epoch processing never verifies)."""
    prev_bls = bls.bls_active
    bls.bls_active = False
    yield
    bls.bls_active = prev_bls
    ek.use_auto()
    state_arrays.use_auto()


def _spec(fork):
    return build_spec(fork, "minimal")


def _genesis(spec):
    return create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * N_VALIDATORS,
        spec.MAX_EFFECTIVE_BALANCE)


def _scatter_registry_edges(spec, state, rng, preserve_active=False):
    """Seed the registry with every eligibility/edge shape the kernels
    branch on.  Mutates fields directly (not via ``slash_validator``)
    so the same scatter works on every fork, whisk included.

    ``preserve_active``: phase0-family states carry pending attestations
    whose aggregation bits were sized against the committees of past
    slots; shapes that change WHO was active then (exits into the past,
    pending activations) would invalidate them for the spec loop too,
    so only activity-preserving shapes are scattered."""
    current_epoch = int(spec.get_current_epoch(state))
    prev_epoch = int(spec.get_previous_epoch(state))
    far = spec.FAR_FUTURE_EPOCH
    slashings_target = current_epoch + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2
    for i in range(len(state.validators)):
        v = state.validators[i]
        roll = rng.random()
        if roll < 0.08:
            # slashed, still delta-eligible (prev + 1 < withdrawable)
            v.slashed = True
            v.withdrawable_epoch = prev_epoch + 2 + rng.randint(0, 3)
        elif roll < 0.12:
            # slashed at the process_slashings target epoch
            v.slashed = True
            v.withdrawable_epoch = slashings_target
        elif roll < 0.16:
            # slashed eligibility BOUNDARY: prev + 1 == withdrawable
            v.slashed = True
            v.withdrawable_epoch = prev_epoch + 1
        elif roll < 0.22:
            # exited / exiting
            v.exit_epoch = current_epoch + rng.randint(1, 3) \
                if preserve_active \
                else max(int(v.activation_epoch) + 1, prev_epoch)
            v.withdrawable_epoch = int(v.exit_epoch) + rng.randint(1, 4)
        elif roll < 0.28:
            # ejection candidate: active at the balance threshold
            v.effective_balance = spec.config.EJECTION_BALANCE
        elif roll < 0.34 and not preserve_active:
            # pending activation right at the finalized boundary
            v.activation_epoch = far
            v.activation_eligibility_epoch = \
                int(state.finalized_checkpoint.epoch) - rng.randint(0, 1) \
                if int(state.finalized_checkpoint.epoch) else 0
        elif roll < 0.40 and not preserve_active:
            # fresh top-up: activation-queue stamp candidate
            v.activation_epoch = far
            v.activation_eligibility_epoch = far
            v.effective_balance = spec.MAX_EFFECTIVE_BALANCE
        # hysteresis-straddling balances (effective-balance updates)
        if rng.random() < 0.6:
            step = int(spec.EFFECTIVE_BALANCE_INCREMENT) \
                // int(spec.HYSTERESIS_QUOTIENT)
            state.balances[i] = max(
                0, int(state.balances[i]) + rng.randint(-3, 3) * step)
    if int(sum(state.slashings)) == 0:
        state.slashings[0] = spec.EFFECTIVE_BALANCE_INCREMENT * 7


def _scatter_participation(spec, state, rng, zero=False):
    for i in range(len(state.validators)):
        prev_flags = 0 if zero else rng.randint(0, 7)
        cur_flags = 0 if zero else rng.randint(0, 7)
        state.previous_epoch_participation[i] = \
            spec.ParticipationFlags(prev_flags)
        state.current_epoch_participation[i] = \
            spec.ParticipationFlags(cur_flags)
        state.inactivity_scores[i] = rng.randint(0, 40)


def _altair_state(fork, *, zero_participation=False, leak=False, seed=7):
    spec = _spec(fork)
    state = _genesis(spec)
    ek.use_loops()
    epochs = 7 if leak else 3
    for _ in range(epochs):
        next_epoch(spec, state)
    if not leak:
        # recent finality: not leaking, and a non-zero finalized epoch
        # for the activation-eligibility boundary
        state.finalized_checkpoint.epoch = spec.get_previous_epoch(state) - 1
    rng = Random(seed)
    _scatter_registry_edges(spec, state, rng)
    _scatter_participation(spec, state, rng, zero=zero_participation)
    assert spec.is_in_inactivity_leak(state) == leak
    return spec, state


def _phase0_state(fork, *, empty_attestations=False, seed=11):
    spec = _spec(fork)
    state = _genesis(spec)
    ek.use_loops()
    next_epoch(spec, state)
    if empty_attestations:
        next_epoch(spec, state)
        next_epoch(spec, state)
    else:
        _, _, state = next_epoch_with_attestations(spec, state, True, False)
        _, _, state = next_epoch_with_attestations(spec, state, True, True)
    rng = Random(seed)
    _scatter_registry_edges(spec, state, rng, preserve_active=True)
    return spec, state


def _assert_function_equivalence(spec, state, fns):
    """Each epoch sub-transition and the full epoch must commit the
    identical post-state through both engines."""
    for fn in fns:
        s_loop, s_vec = state.copy(), state.copy()
        ek.use_loops()
        run_epoch_processing_to(spec, s_loop, fn)
        getattr(spec, fn)(s_loop)
        ek.use_vectorized()
        with counting() as delta:
            run_epoch_processing_to(spec, s_vec, fn)
            getattr(spec, fn)(s_vec)
        assert delta["epoch.transition{path=vectorized}"] > 0, \
            f"{spec.fork}.{fn}: vectorized engine never committed"
        assert delta["epoch.fallbacks{reason=guard}"] == 0, \
            f"{spec.fork}.{fn}: unexpected guard fallback"
        assert hash_tree_root(s_loop) == hash_tree_root(s_vec), \
            f"{spec.fork}.{fn}: post-state roots diverge"
    s_loop, s_vec = state.copy(), state.copy()
    ek.use_loops()
    spec.process_epoch(s_loop)
    ek.use_vectorized()
    spec.process_epoch(s_vec)
    assert hash_tree_root(s_loop) == hash_tree_root(s_vec), \
        f"{spec.fork}: full process_epoch roots diverge"


@pytest.mark.parametrize("fork", ALTAIR_FAMILY)
def test_altair_family_differential(fork):
    spec, state = _altair_state(fork)
    _assert_function_equivalence(spec, state, ALTAIR_VECTORIZED_FNS)


@pytest.mark.parametrize("fork", PHASE0_FAMILY)
def test_phase0_family_differential(fork):
    spec, state = _phase0_state(fork)
    _assert_function_equivalence(spec, state, VECTORIZED_FNS)


@pytest.mark.parametrize("fork", ["altair", "deneb"])
def test_zero_participation_epoch(fork):
    spec, state = _altair_state(fork, zero_participation=True, seed=13)
    _assert_function_equivalence(spec, state, ALTAIR_VECTORIZED_FNS)


def test_phase0_no_attestations_epoch():
    spec, state = _phase0_state("phase0", empty_attestations=True, seed=17)
    _assert_function_equivalence(spec, state, VECTORIZED_FNS)


@pytest.mark.parametrize("fork", ["altair", "phase0"])
def test_inactivity_leak_epoch(fork):
    if fork == "phase0":
        spec = _spec(fork)
        state = _genesis(spec)
        ek.use_loops()
        next_epoch(spec, state)
        _, _, state = next_epoch_with_attestations(spec, state, True, False)
        for _ in range(6):     # let finality lapse into a leak
            next_epoch(spec, state)
        _, _, state = next_epoch_with_attestations(spec, state, True, False)
        _scatter_registry_edges(spec, state, Random(19), preserve_active=True)
        assert spec.is_in_inactivity_leak(state)
        _assert_function_equivalence(spec, state, VECTORIZED_FNS)
    else:
        spec, state = _altair_state(fork, leak=True, seed=23)
        _assert_function_equivalence(spec, state, ALTAIR_VECTORIZED_FNS)


def test_guard_fallback_matches_loop():
    """A state that could overflow a uint64 lane must fall back to the
    spec loop — and the fallback result must equal a forced-loop run."""
    spec, state = _altair_state("altair", seed=29)
    # big inactivity score: eff * score overflows the intermediate uint64
    # lane (trips the engine's guard) while the final penalty still fits,
    # so the per-validator spec loop processes the state normally
    state.inactivity_scores[3] = 10**9
    s_loop, s_vec = state.copy(), state.copy()
    ek.use_loops()
    spec.process_rewards_and_penalties(s_loop)
    ek.use_vectorized()
    with counting() as delta:
        spec.process_rewards_and_penalties(s_vec)
    assert delta["epoch.fallbacks{reason=guard}"] == 1
    assert hash_tree_root(s_loop) == hash_tree_root(s_vec)


@pytest.mark.parametrize("store_on", [True, False])
def test_registry_poisoning_mid_epoch(store_on):
    """Cache-poisoning regression (the PR-4-review bug shape): mutate
    the registry through the SSZ sequence API BETWEEN kernel reads of
    one epoch, with warm columns.  The next kernel read must see fresh
    columns — the StateArrays store revalidates against the sequence
    mutation generation (store on) or re-extracts per call (store off);
    a stale snapshot would keep validator 5's old effective balance and
    commit a divergent post-state."""
    (state_arrays.use_arrays if store_on else state_arrays.use_fallback)()
    spec, state = _altair_state("altair", seed=43)
    s_loop, s_vec = state.copy(), state.copy()

    ek.use_vectorized()
    with counting() as delta:
        assert ek.try_process_rewards_and_penalties(spec, s_vec)
    assert delta["cache.miss{cache=state_arrays}"] > 0   # columns warmed
    # poison: a raw SSZ write the engine never saw
    s_vec.validators[5].effective_balance = spec.EFFECTIVE_BALANCE_INCREMENT
    assert ek.try_process_effective_balance_updates(spec, s_vec)

    ek.use_loops()
    spec.process_rewards_and_penalties(s_loop)
    s_loop.validators[5].effective_balance = spec.EFFECTIVE_BALANCE_INCREMENT
    spec.process_effective_balance_updates(s_loop)

    assert hash_tree_root(s_loop) == hash_tree_root(s_vec), \
        f"store_on={store_on}: stale registry columns after SSZ mutation"


def test_env_flag_disables_auto(monkeypatch):
    spec, state = _altair_state("altair", seed=31)
    monkeypatch.setenv("CS_TPU_VECTORIZED_EPOCH", "0")
    ek.use_auto()
    assert not ek.enabled()
    assert ek.backend_name() == "loops"
    assert not ek.try_process_rewards_and_penalties(spec, state)
    # the live switch must flip back on without a reimport — asserted
    # with an explicit "1" so the test also holds on the
    # CS_TPU_VECTORIZED_EPOCH=0 CI off-leg, where the import-time
    # default (what an unset variable falls back to) is off
    monkeypatch.setenv("CS_TPU_VECTORIZED_EPOCH", "1")
    assert ek.enabled()
    assert ek.backend_name() == "vectorized"
    # unset restores the import-time default, whatever it was
    monkeypatch.delenv("CS_TPU_VECTORIZED_EPOCH")
    from consensus_specs_tpu.utils import env_flags
    assert ek.enabled() == \
        env_flags._SWITCH_DEFAULTS["CS_TPU_VECTORIZED_EPOCH"]


def test_registry_churn_pressure():
    """More ejections and activations than one epoch's churn: the
    incremental exit-queue simulation must match the spec recurrence."""
    spec = _spec("deneb")
    state = _genesis(spec)
    ek.use_loops()
    for _ in range(3):
        next_epoch(spec, state)
    state.finalized_checkpoint.epoch = spec.get_previous_epoch(state) - 1
    far = spec.FAR_FUTURE_EPOCH
    for i in range(len(state.validators)):
        v = state.validators[i]
        if i % 3 == 0:
            v.effective_balance = spec.config.EJECTION_BALANCE  # eject
        elif i % 3 == 1:
            v.activation_epoch = far                            # activate
            v.activation_eligibility_epoch = \
                state.finalized_checkpoint.epoch
    _scatter_participation(spec, state, Random(37))
    _assert_function_equivalence(spec, state, ["process_registry_updates"])


def test_write_back_wholesale_matches_targeted():
    """Both _write_u64_list strategies (targeted ``__setitem__`` vs
    wholesale item swap, dedup-pool and direct-build variants) must
    produce the same list content and root as plain per-index writes."""
    BalanceList = List[uint64, 1 << 40]
    rng = Random(41)
    n = 512
    base = [rng.randrange(0, 2**40) for _ in range(n)]

    def reference(new_vals):
        ref = BalanceList(base)
        for i, v in enumerate(new_vals):
            ref[i] = uint64(v)
        return hash_tree_root(ref)

    # targeted: a handful of changes
    few = list(base)
    few[3], few[200] = few[3] + 1, 0
    # wholesale + dedup pool: everything changes, few distinct values
    pooled = [base[i] % 5 for i in range(n)]
    # wholesale direct: everything changes, all-distinct values
    distinct = [base[i] + i + 1 for i in range(n)]
    for new_vals in (few, pooled, distinct):
        seq = BalanceList(base)
        ek._write_u64_list(
            seq, uint64,
            np.array(base, dtype=np.uint64), np.array(new_vals, dtype=np.uint64))
        assert [int(x) for x in seq] == [int(v) for v in new_vals]
        assert hash_tree_root(seq) == reference(new_vals)


def test_kernels_jit_under_jax():
    """The pure kernels must produce identical uint64 lanes under
    ``jax.jit`` (device dispatch path) as under numpy."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        rng = np.random.default_rng(43)
        n = 256
        balances = rng.integers(0, 2**35, n, dtype=np.uint64)
        rewards = rng.integers(0, 2**20, n, dtype=np.uint64)
        penalties = rng.integers(0, 2**36, n, dtype=np.uint64)
        eff = rng.integers(1, 32, n, dtype=np.uint64) * np.uint64(10**9)
        scores = rng.integers(0, 50, n, dtype=np.uint64)
        eligible = rng.random(n) < 0.8
        participating = rng.random(n) < 0.6

        host = ek.apply_deltas_kernel(np, balances, rewards, penalties)
        dev = jax.jit(lambda b, r, p: ek.apply_deltas_kernel(jnp, b, r, p))(
            balances, rewards, penalties)
        np.testing.assert_array_equal(host, np.asarray(dev))

        kw = dict(increment=10**9, downward_threshold=2 * 10**8,
                  upward_threshold=5 * 10**8,
                  max_effective_balance=32 * 10**9)
        host = ek.effective_balance_kernel(np, balances, eff, **kw)
        dev = jax.jit(lambda b, e: ek.effective_balance_kernel(
            jnp, b, e, **kw))(balances, eff)
        np.testing.assert_array_equal(host, np.asarray(dev))

        kw = dict(bias=4, recovery_rate=16, in_leak=False)
        host = ek.inactivity_updates_kernel(
            np, scores, eligible, participating, **kw)
        dev = jax.jit(lambda s, e, p: ek.inactivity_updates_kernel(
            jnp, s, e, p, **kw))(scores, eligible, participating)
        np.testing.assert_array_equal(host, np.asarray(dev))
    finally:
        jax.config.update("jax_enable_x64", prev_x64)


def test_declining_guard_runs_each_hand_body_once():
    """The hand-written altair epoch bodies carry no dispatch of their
    own: with every site declining (an injected fault at each),
    ``process_epoch`` dispatches each sub-transition once and runs its
    spec body once — one counted fallback per site, and exactly the
    forced-loop post-state."""
    spec, state = _altair_state("altair", seed=31)
    s_loop, s_vec = state.copy(), state.copy()
    ek.use_loops()
    spec.process_epoch(s_loop)
    ek.use_vectorized()
    sites = [site for site in faults.SITES if site.startswith("epoch.")]
    assert len(sites) == len(ALTAIR_VECTORIZED_FNS)
    sched = faults.FaultSchedule(triggers={site: {1} for site in sites})
    with counting() as delta, faults.injected(sched):
        spec.process_epoch(s_vec)
    assert sched.calls == {site: 1 for site in sites}
    assert sched.fully_fired()
    assert delta["epoch.fallbacks{reason=injected}"] == len(sites)
    assert delta["epoch.transition{path=loop}"] == len(sites)
    assert delta["epoch.transition{path=vectorized}"] == 0
    assert hash_tree_root(s_loop) == hash_tree_root(s_vec)


def test_install_vectorized_epoch_idempotent():
    calls = []

    class FakeSpec:
        fork = "phase0"

        def process_slashings(self, state):
            calls.append("loop")

    ek.install_vectorized_epoch(FakeSpec)
    wrapped_once = FakeSpec.__dict__["process_slashings"]
    ek.install_vectorized_epoch(FakeSpec)
    assert FakeSpec.__dict__["process_slashings"] is wrapped_once
    assert wrapped_once._vectorized_epoch_wrapper

    ek.use_loops()     # dispatch declines -> the original body runs
    FakeSpec().process_slashings(None)
    assert calls == ["loop"]


def test_compiled_ladder_vectorized_differential():
    """``install_vectorized_epoch`` routes the engine into the markdown-
    compiled ladder (through ``forks.install_engines``, the wiring
    ``use_compiled_registry`` uses): the wrapped compiled altair spec
    must commit the same full-epoch post-state through the array engine
    as through its verbatim-emitted loops."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-m", "consensus_specs_tpu.compiler"],
                   check=True, cwd=repo, capture_output=True)
    from consensus_specs_tpu.config import load_config, load_preset
    from consensus_specs_tpu.forks import install_engines
    from consensus_specs_tpu.forks.compiled.altair import CompiledAltairSpec
    # wrap the whole lineage: process_effective_balance_updates lives on
    # the compiled phase0 base, not on the altair class itself
    for klass in CompiledAltairSpec.__mro__:
        if isinstance(klass.__dict__.get("fork"), str):
            install_engines(klass)
    spec = CompiledAltairSpec(load_preset("minimal"), load_config("minimal"),
                              preset_name="minimal")
    state = _genesis(spec)
    ek.use_loops()
    for _ in range(3):
        next_epoch(spec, state)
    state.finalized_checkpoint.epoch = spec.get_previous_epoch(state) - 1
    rng = Random(47)
    _scatter_registry_edges(spec, state, rng)
    _scatter_participation(spec, state, rng)
    s_loop, s_vec = state.copy(), state.copy()
    ek.use_loops()
    spec.process_epoch(s_loop)
    ek.use_vectorized()
    with counting() as delta:
        spec.process_epoch(s_vec)
    assert delta["epoch.transition{path=vectorized}"] > 0, \
        "compiled ladder never dispatched to the vectorized engine"
    assert hash_tree_root(s_loop) == hash_tree_root(s_vec), \
        "compiled-ladder post-state roots diverge"


def test_epoch_ordering_covers_vectorized_fns():
    """Every function the engine vectorizes appears in each fork's
    epoch ordering (guards the dispatch wiring against reorderings)."""
    for fork in PHASE0_FAMILY + ALTAIR_FAMILY:
        calls = get_process_calls(_spec(fork))
        expected = VECTORIZED_FNS if fork in PHASE0_FAMILY \
            else ALTAIR_VECTORIZED_FNS
        for fn in expected:
            assert fn in calls, (fork, fn)


# ---------------------------------------------------------------------------
# speclint uint64-hazard regressions: the real findings the U1xx pass
# surfaced in ops/epoch_kernels.py, each pinned against the spec-loop
# oracle at the shape that makes the fixed/annotated line load-bearing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fork", ["phase0", "deneb"])
def test_registry_mass_ejection_sum_dtype_regression(fork):
    """Pins the explicit-dtype reductions in ``_registry_updates``
    (active-set churn limit and exit-queue churn counter, both formerly
    dtype-less bool ``.sum()``s): eject half the registry so the churn
    recurrence advances ``queue_epoch`` repeatedly — every advance
    consumes both counts — and require bit-identical post-state."""
    spec = _spec(fork)
    state = _genesis(spec)
    ek.use_loops()
    next_epoch(spec, state)
    for i in range(0, len(state.validators), 2):
        state.validators[i].effective_balance = spec.config.EJECTION_BALANCE
    s_loop, s_vec = state.copy(), state.copy()
    ek.use_loops()
    spec.process_registry_updates(s_loop)
    ek.use_vectorized()
    with counting() as delta:
        spec.process_registry_updates(s_vec)
    assert delta["epoch.transition{path=vectorized}"] == 1
    assert delta["epoch.fallbacks{reason=guard}"] == 0
    assert hash_tree_root(s_loop) == hash_tree_root(s_vec)
    # the queue really did saturate: ejections spread over >= 2 epochs,
    # so the per-epoch churn counter (the second fixed sum) was consumed
    exits = {int(v.exit_epoch) for v in s_vec.validators
             if v.exit_epoch != spec.FAR_FUTURE_EPOCH}
    assert len(exits) >= 2


def test_phase0_minimal_balance_reward_bounds_regression():
    """Pins the ``max_attester = base_reward - proposer_reward``
    unsigned subtraction (# noqa: U101): at one-increment effective
    balances ``base_reward`` is at its minimum and the proposer cut
    rounds to its extreme relative value — the lane must not wrap."""
    spec, state = _phase0_state("phase0", seed=31)
    for i in range(len(state.validators)):
        state.validators[i].effective_balance = \
            spec.EFFECTIVE_BALANCE_INCREMENT
    _assert_function_equivalence(spec, state,
                                 ["process_rewards_and_penalties"])


def test_phase0_leak_minimal_balance_base_pen_regression():
    """Pins the ``base_pen = BASE_REWARDS_PER_EPOCH * base_reward -
    proposer_reward`` unsigned subtraction (# noqa: U101), which only
    runs in an inactivity leak, at minimum-balance extremes."""
    spec = _spec("phase0")
    state = _genesis(spec)
    ek.use_loops()
    next_epoch(spec, state)
    _, _, state = next_epoch_with_attestations(spec, state, True, False)
    for _ in range(6):     # let finality lapse into a leak
        next_epoch(spec, state)
    _, _, state = next_epoch_with_attestations(spec, state, True, False)
    for i in range(len(state.validators)):
        state.validators[i].effective_balance = \
            spec.EFFECTIVE_BALANCE_INCREMENT
    assert spec.is_in_inactivity_leak(state)
    _assert_function_equivalence(spec, state,
                                 ["process_rewards_and_penalties"])
