"""Vectorized epoch-processing engine: array-native rewards, penalties
and balance updates.

The per-validator epoch loops (``process_rewards_and_penalties``,
``process_inactivity_updates``, ``process_effective_balance_updates``,
``process_registry_updates`` eligibility scans, ``process_slashings``)
are O(validators) python iterations over SSZ typed views — the last
python-loop-bound hot path at registry scale (BASELINE.md config #5:
the 1M-validator epoch transition is all epoch-loop time).  This module
re-expresses them as columnar array kernels over the canonical
struct-of-arrays state store (``state/arrays.py``): columns are
extracted once per state lineage, revalidated structurally against the
SSZ mutation generations, mutated copy-on-write by the kernels, and —
inside the ``state_arrays.commit_scope`` that
:func:`install_vectorized_epoch` opens around ``process_epoch`` —
committed back to SSZ chunks once per epoch transition instead of once
per sub-transition.

Layering mirrors the BLS backend switch (``utils/bls.py``):

  use_vectorized() / use_loops() / use_auto()   runtime switch; auto
      (the default) is ON unless ``CS_TPU_VECTORIZED_EPOCH=0``
  try_process_*(spec, state) -> bool            per-method dispatch; True
      means the vectorized engine committed the transition, False means
      "run the spec body" (switch off, genesis no-op, or a guard tripped)
  install_vectorized_epoch(cls)                 wraps a spec class's own
      epoch methods with that dispatch from outside, on both ladders
      (``forks.install_engines``), so the method bodies stay spec-shaped

Exactness contract: every kernel reproduces the spec loops bit-for-bit
— same uint64 truncations, same clamp-at-zero balance decreases, same
ordering — so post-state ``hash_tree_root`` is identical (enforced by
``tests/test_epoch_vectorized.py``).  All intermediate products are
range-checked against 2**64 with python-int bounds before any array op
runs; a state that could overflow a uint64 lane falls back to the spec
loop instead of wrapping.

The kernels themselves (``*_kernel``) are pure functions of arrays and
python scalars written against an ``xp`` array namespace: ``numpy`` on
the host (the production CPU path) and ``jax.numpy`` under ``jax.jit``
for device dispatch (uint64 lanes need ``jax_enable_x64``).
"""
import math

import numpy as np

from consensus_specs_tpu import faults, supervisor
from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.utils import env_flags

from consensus_specs_tpu.state import arrays as state_arrays
# shared commit/extraction primitives live in the state layer now;
# re-exported here because the merkle bench smoke and older call sites
# import them under these names
from consensus_specs_tpu.state.arrays import (   # noqa: F401
    u64_column, _write_u64_list)
from consensus_specs_tpu.utils.ssz import sequence_items

_U64_MAX = (1 << 64) - 1

# ---------------------------------------------------------------------------
# Runtime switch (mirrors utils/bls.py's use_py/use_jax/use_fastest)
# ---------------------------------------------------------------------------

_mode = "auto"


def use_vectorized() -> None:
    """Force the array engine on (guards can still fall back)."""
    global _mode
    _mode = "on"


def use_loops() -> None:
    """Force the per-validator spec loops (the differential oracle)."""
    global _mode
    _mode = "off"


def use_auto() -> None:
    """Default policy: on unless ``CS_TPU_VECTORIZED_EPOCH=0``."""
    global _mode
    _mode = "auto"


def backend_name() -> str:
    return "loops" if not enabled() else "vectorized"


def enabled() -> bool:
    if _mode == "on":
        return True
    if _mode == "off":
        return False
    return env_flags.switch("CS_TPU_VECTORIZED_EPOCH")


# vectorized-commit / guard-fallback counters; the differential suite
# asserts on these so a silent fallback cannot turn its comparisons
# into loop-vs-loop tautologies.  Registered in the obs metrics registry
# as ``epoch.transition{path=vectorized|loop}`` plus a dedicated
# guard-trip counter (series pre-bound, speclint O5xx hot-path rule).
# ``path=loop`` counts every transition the spec loop ended up running
# (engine off, genesis no-op, or a guard trip); ``epoch.fallbacks
# {reason=guard|injected}`` counts only the trips among them — organic
# guard refusals vs faults injected by the adversarial harness
# (``consensus_specs_tpu/faults.py``).
_C_EPOCH_VECTORIZED = obs_registry.counter(
    "epoch.transition").labels(path="vectorized")
_C_EPOCH_LOOP = obs_registry.counter("epoch.transition").labels(path="loop")
_C_EPOCH_FALLBACKS_ALL = obs_registry.counter("epoch.fallbacks")
_EPOCH_FALLBACKS = {
    "guard": _C_EPOCH_FALLBACKS_ALL.labels(reason="guard"),
    "injected": _C_EPOCH_FALLBACKS_ALL.labels(reason="injected"),
    "deadline": _C_EPOCH_FALLBACKS_ALL.labels(reason="deadline"),
}


def stats() -> dict:
    """Back-compat alias view of the ``epoch.*`` registry metrics (the
    differential suite asserts on these keys)."""
    return {"vectorized": _C_EPOCH_VECTORIZED.n,
            "fallback": _C_EPOCH_FALLBACKS_ALL.total()}


def reset_stats() -> None:
    obs_registry.reset("epoch.")


class _Fallback(Exception):
    """A guard refused the array path (possible uint64 overflow or an
    unsupported shape); the caller runs the spec loop instead."""


def _guard(*products) -> None:
    """Fail over to the spec loop if any python-int bound reaches 2**64."""
    for p in products:
        if p > _U64_MAX:
            raise _Fallback()


# ---------------------------------------------------------------------------
# Struct-of-arrays state access (state/arrays.py)
# ---------------------------------------------------------------------------
#
# The registry snapshot, balance and participation columns all come from
# the state's attached copy-on-write ``StateArrays`` store: extracted
# once per state lineage, revalidated against the SSZ sequences'
# mutation generations (a write through the sequence API bumps the
# generation, so a stale column is structurally impossible), and — when
# the ``commit_scope`` installed around ``process_epoch`` is open —
# committed back to SSZ chunks once per transition.  The root-keyed
# ``_COLS_CACHE`` LRU this module used to keep is gone; every guard
# fallback flushes pending column writes first so the spec loop always
# reads fresh SSZ.


# ---------------------------------------------------------------------------
# Pure array kernels (xp = numpy on host, jax.numpy under jit)
# ---------------------------------------------------------------------------

def apply_deltas_kernel(xp, balances, rewards, penalties):
    """increase_balance then clamped decrease_balance, per validator."""
    up = balances + rewards
    return xp.where(penalties > up, xp.uint64(0), up - penalties)


# speclint: guarded-by-caller (try_process_* bounds every product < 2**64)
def flag_deltas_kernel(xp, base_reward, eligible, participating, *,
                       weight, weight_denominator, participating_increments,
                       active_increments, in_leak, is_head_flag):
    """altair ``get_flag_index_deltas`` for one participation flag."""
    zero = xp.uint64(0)
    reward = (base_reward * xp.uint64(weight)
              * xp.uint64(participating_increments)) \
        // xp.uint64(active_increments * weight_denominator)
    rewards = xp.where(eligible & participating & (not in_leak), reward, zero)
    penalty = (base_reward * xp.uint64(weight)) // xp.uint64(weight_denominator)
    penalize = eligible & ~participating & (not is_head_flag)
    penalties = xp.where(penalize, penalty, zero)
    return rewards, penalties


# speclint: guarded-by-caller (try_process_* bounds eff * scores < 2**64)
def inactivity_penalty_kernel(xp, eff, scores, eligible, target_participating,
                              *, denominator):
    """altair+ ``get_inactivity_penalty_deltas`` (score-scaled)."""
    penalty = (eff * scores) // xp.uint64(denominator)
    return xp.where(eligible & ~target_participating, penalty, xp.uint64(0))


def inactivity_updates_kernel(xp, scores, eligible, participating, *,
                              bias, recovery_rate, in_leak):
    """altair ``process_inactivity_updates`` score transition."""
    one = xp.uint64(1)
    bumped = xp.where(participating, scores - xp.minimum(one, scores),
                      scores + xp.uint64(bias))
    if not in_leak:
        rec = xp.uint64(recovery_rate)
        bumped = bumped - xp.minimum(rec, bumped)
    return xp.where(eligible, bumped, scores)


# speclint: guarded-by-caller (br_max * att_increments bounded < 2**64)
def phase0_component_kernel(xp, base_reward, eligible, attesting, *,
                            in_leak, attesting_increments, total_increments):
    """phase0 ``get_attestation_component_deltas`` (source/target/head)."""
    zero = xp.uint64(0)
    if in_leak:
        # full base reward; canceled later by the inactivity deltas
        reward = base_reward
    else:
        reward = (base_reward * xp.uint64(attesting_increments)) \
            // xp.uint64(total_increments)
    rewards = xp.where(eligible & attesting, reward, zero)
    penalties = xp.where(eligible & ~attesting, base_reward, zero)
    return rewards, penalties


# speclint: guarded-by-caller (base_pen + extra bounded together < 2**64)
# speclint: invariant: base_rewards_per_epoch >= 1
# speclint: invariant: proposer_reward_quotient >= 1
def phase0_inactivity_kernel(xp, base_reward, eff, eligible,
                             target_attesting, *, base_rewards_per_epoch,
                             proposer_reward_quotient, finality_delay,
                             inactivity_penalty_quotient):
    """phase0 ``get_inactivity_penalty_deltas`` (leak epochs only)."""
    zero = xp.uint64(0)
    proposer_reward = base_reward // xp.uint64(proposer_reward_quotient)
    # machine-checked safe (speclint U9xx range prover): the declared
    # invariants give proposer_reward <= base_reward <= brpe*base_reward
    base_pen = (xp.uint64(base_rewards_per_epoch) * base_reward
                - proposer_reward)
    extra = (eff * xp.uint64(finality_delay)) \
        // xp.uint64(inactivity_penalty_quotient)
    pen = base_pen + xp.where(target_attesting, zero, extra)
    return xp.where(eligible, pen, zero)


def effective_balance_kernel(xp, balances, eff, *, increment,
                             downward_threshold, upward_threshold,
                             max_effective_balance):
    """``process_effective_balance_updates`` hysteresis."""
    crossed = ((balances + xp.uint64(downward_threshold) < eff)
               | (eff + xp.uint64(upward_threshold) < balances))
    capped = xp.minimum(balances - balances % xp.uint64(increment),
                        xp.uint64(max_effective_balance))
    return xp.where(crossed, capped, eff)


# speclint: guarded-by-caller ((eff // increment) * adjusted bounded < 2**64)
def slashing_penalty_kernel(xp, eff, target, *, increment,
                            adjusted_total_slashing_balance, total_balance):
    """``process_slashings`` penalty column (spec's truncation order:
    divide by total_balance BEFORE multiplying back by increment)."""
    numer = (eff // xp.uint64(increment)) \
        * xp.uint64(adjusted_total_slashing_balance)
    penalty = (numer // xp.uint64(total_balance)) * xp.uint64(increment)
    return xp.where(target, penalty, xp.uint64(0))


# ---------------------------------------------------------------------------
# Scalar plumbing shared by the orchestrators
# ---------------------------------------------------------------------------

def _fork_lineage(spec) -> frozenset:
    """Fork names along the spec class's inheritance chain — works for
    the hand-written and the markdown-compiled ladder alike (both stamp
    ``fork`` on every class)."""
    return frozenset(
        c.__dict__["fork"] for c in type(spec).__mro__
        if isinstance(c.__dict__.get("fork"), str))


def _masked_sum(eff, mask) -> int:
    """Exact python-int sum of a masked uint64 column."""
    sub = eff[mask]
    if sub.size == 0:
        return 0
    mx = int(sub.max())
    if mx and sub.size > _U64_MAX // mx:
        return sum(int(x) for x in sub.tolist())
    return int(sub.sum(dtype=np.uint64))


def _epoch_masks(spec, cols, previous_epoch):
    """active-at-previous-epoch and reward-eligibility masks
    (``get_eligible_validator_indices``)."""
    prev = np.uint64(previous_epoch)
    active_prev = (cols["act"] <= prev) & (prev < cols["ext"])
    eligible = active_prev | (cols["sl"] & (previous_epoch + 1 < cols["wd"]))
    return active_prev, eligible


def _total_active_balance(spec, cols, current_epoch) -> int:
    """``get_total_active_balance`` from columns (same increment clamp)."""
    cur = np.uint64(current_epoch)
    active = (cols["act"] <= cur) & (cur < cols["ext"])
    return max(int(spec.EFFECTIVE_BALANCE_INCREMENT),
               _masked_sum(cols["eff"], active))


def _mask_from_indices(n, indices) -> np.ndarray:
    mask = np.zeros(n, dtype=bool)
    if indices:
        mask[np.fromiter(indices, dtype=np.int64, count=len(indices))] = True
    return mask


# ---------------------------------------------------------------------------
# Supervised dispatch plumbing (shared by the five try_process_* sites)
# ---------------------------------------------------------------------------

def _audited(spec, state, site, method_name, fast_fn) -> bool:
    """Sentinel-audited epoch call (``supervisor.audit_due``): the spec
    loop runs on the REAL state — its result is authoritative, so a
    silently-wrong kernel cannot leak into the chain even on the
    audited call itself — while the vectorized kernel runs on a
    throwaway copy, and the two post-states must merkleize
    byte-identical.  A mismatch quarantines the site.  Returns True:
    the sub-transition has been applied (by the spec loop) either way,
    so the caller must not run its body again."""
    from consensus_specs_tpu.utils.ssz import hash_tree_root
    probe = state.copy()
    handled = False
    try:
        faults.check(site)
        with supervisor.deadline_scope(site):
            handled = fast_fn(spec, probe)
    except (_Fallback, faults.InjectedFault,
            supervisor.DeadlineExceeded) as exc:
        faults.count_fallback(_EPOCH_FALLBACKS, exc, site=site)
    # the spec loop, via the wrapped spec method:
    # probing() makes every try_process_* decline — which books the
    # path=loop counter and flushes pending columns itself (_decline),
    # so this helper must NOT double-book either — and the replay
    # really is the per-validator loop, never recursing into the kernel
    with supervisor.probe():
        getattr(spec, method_name)(state)
    if handled:
        state_arrays.flush(probe)
        ok = bytes(hash_tree_root(probe)) == bytes(hash_tree_root(state))
        supervisor.audit_result(
            site, ok, f"vectorized {method_name} post-state root "
            "diverged from the spec loop")
    return True


def _decline(state) -> bool:
    """The common spec-loop decline bookkeeping (returns False)."""
    state_arrays.flush(state)
    _C_EPOCH_LOOP.add()
    return False


def _supervised(spec, state, site, method_name, fast_fn) -> bool:
    """The shared supervised-dispatch skeleton behind every
    try_process_* site (the per-site wrappers keep only their
    site-specific no-op pre-checks): breaker admission, sentinel-audit
    sampling, the fault hook + deadline scope around the kernel body,
    counted fallback on any fallback-class trip, health reporting on
    success.  ``fast_fn`` returns False when the kernel has nothing to
    do (the caller's spec body runs instead, no fallback implied)."""
    if not supervisor.admit(site):
        return _decline(state)
    if supervisor.audit_due(site):
        return _audited(spec, state, site, method_name, fast_fn)
    try:
        faults.check(site)
        with supervisor.deadline_scope(site):
            if not fast_fn(spec, state):
                _C_EPOCH_LOOP.add()
                return False
    except (_Fallback, faults.InjectedFault,
            supervisor.DeadlineExceeded) as exc:
        state_arrays.flush(state)
        faults.count_fallback(_EPOCH_FALLBACKS, exc, site=site)
        _C_EPOCH_LOOP.add()
        return False
    supervisor.note_success(site)
    _C_EPOCH_VECTORIZED.add()
    return True


# ---------------------------------------------------------------------------
# process_rewards_and_penalties
# ---------------------------------------------------------------------------

def _fast_rewards_and_penalties(spec, state) -> bool:
    from consensus_specs_tpu.parallel import mesh_epoch
    if mesh_epoch.try_rewards_and_penalties(spec, state):
        pass    # SPMD program committed the balance column
    elif "altair" in _fork_lineage(spec):
        _altair_rewards_and_penalties(spec, state)
    else:
        _phase0_rewards_and_penalties(spec, state)
    if faults.corrupt_armed("epoch.rewards_and_penalties"):
        # silent-corruption injection (sentinel-audit test vector):
        # one gwei on validator 0, exactly the class of wrongness a
        # counted fallback can never surface
        sa = state_arrays.of(state)
        balances = sa.balances().copy()
        if balances.size:
            balances[0] += np.uint64(1)
            sa.set_balances(balances)
    return True


def try_process_rewards_and_penalties(spec, state) -> bool:
    if not enabled() or supervisor.probing():
        return _decline(state)
    if spec.get_current_epoch(state) == spec.GENESIS_EPOCH:
        _C_EPOCH_LOOP.add()
        return False    # the spec body is already a no-op early return
    return _supervised(spec, state, "epoch.rewards_and_penalties",
                       "process_rewards_and_penalties",
                       _fast_rewards_and_penalties)


def _base_reward_phase0(spec, cols, total_balance):
    """phase0 ``get_base_reward`` column + its python-int max bound."""
    sqrt_total = spec.integer_squareroot(total_balance)
    brf = int(spec.BASE_REWARD_FACTOR)
    brpe = int(spec.BASE_REWARDS_PER_EPOCH)
    max_eff = int(cols["eff"].max(initial=0))
    _guard(max_eff * brf)
    base_reward = (cols["eff"] * np.uint64(brf)) \
        // np.uint64(int(sqrt_total)) // np.uint64(brpe)
    return base_reward, max_eff * brf // int(sqrt_total) // brpe


def _phase0_rewards_and_penalties(spec, state) -> None:
    """``get_attestation_deltas`` + the balance-update loop, columnar.
    The O(attestations) committee work stays in python (it is already
    cached and small); every O(validators) pass runs as an array op."""
    xp = np
    prev_epoch = spec.get_previous_epoch(state)
    # spec helpers up front: their assertion behavior (exception as
    # invalidity) must fire exactly as in the loop path, before any write
    src_atts = spec.get_matching_source_attestations(state, prev_epoch)
    tgt_atts = spec.get_matching_target_attestations(state, prev_epoch)
    head_atts = spec.get_matching_head_attestations(state, prev_epoch)
    src_set = spec.get_unslashed_attesting_indices(state, src_atts)
    tgt_set = spec.get_unslashed_attesting_indices(state, tgt_atts)
    head_set = spec.get_unslashed_attesting_indices(state, head_atts)

    sa = state_arrays.of(state)
    cols = sa.registry()
    n = len(cols)
    if n == 0:
        return
    eff = cols["eff"]
    _, eligible = _epoch_masks(spec, cols, int(prev_epoch))
    total_balance = _total_active_balance(spec, cols,
                                          int(spec.get_current_epoch(state)))
    _guard(total_balance)
    increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    total_increments = total_balance // increment
    in_leak = bool(spec.is_in_inactivity_leak(state))
    base_reward, br_max = _base_reward_phase0(spec, cols, total_balance)

    reward_parts, penalty_parts = [], []
    for att_set in (src_set, tgt_set, head_set):
        att_mask = _mask_from_indices(n, att_set)
        att_balance = max(increment, _masked_sum(eff, att_mask))
        att_increments = att_balance // increment
        _guard(br_max * att_increments)
        r, p = phase0_component_kernel(
            xp, base_reward, eligible, att_mask, in_leak=in_leak,
            attesting_increments=att_increments,
            total_increments=total_increments)
        reward_parts.append(r)
        penalty_parts.append(p)

    # cooperative deadline boundary between the component kernels and
    # the inclusion-delay pass (scope armed by the try_process wrapper)
    supervisor.deadline_check()
    # inclusion-delay rewards: one ordered pass over the source
    # attestations finds each attester's earliest-included attestation
    # (the spec's min() keeps the first minimum, hence the strict <)
    # speclint: invariant: prq >= 1
    prq = int(spec.PROPOSER_REWARD_QUOTIENT)
    src_mask = _mask_from_indices(n, src_set)
    best_delay = np.full(n, _U64_MAX, dtype=np.uint64)
    best_proposer = np.zeros(n, dtype=np.int64)
    for att in src_atts:
        idxs = spec.get_attesting_indices(state, att.data,
                                          att.aggregation_bits)
        if not idxs:
            continue
        ii = np.fromiter(idxs, dtype=np.int64, count=len(idxs))
        upd = np.uint64(int(att.inclusion_delay)) < best_delay[ii]
        sel = ii[upd]
        best_delay[sel] = np.uint64(int(att.inclusion_delay))
        best_proposer[sel] = int(att.proposer_index)
    proposer_reward = base_reward // np.uint64(prq)
    incl_rewards = np.zeros(n, dtype=np.uint64)
    src_idx = np.nonzero(src_mask)[0]
    if src_idx.size:
        # machine-checked safe (speclint U9xx range prover):
        # proposer_reward = base_reward // prq <= base_reward with the
        # declared prq >= 1 invariant, preserved under the shared index
        max_attester = (base_reward[src_idx]
                        - proposer_reward[src_idx])
        incl_rewards[src_idx] = max_attester // best_delay[src_idx]
        # every attester's proposer cut could land on ONE proposer index
        _guard(br_max + src_idx.size * (br_max // prq))
        np.add.at(incl_rewards, best_proposer[src_idx],
                  proposer_reward[src_idx])
    reward_parts.append(incl_rewards)

    # inactivity penalties (leak epochs)
    if in_leak:
        finality_delay = int(spec.get_finality_delay(state))
        tgt_mask = _mask_from_indices(n, tgt_set)
        max_eff = int(eff.max(initial=0))
        # base_pen + extra is one uint64 lane sum: bound the two together
        _guard(int(spec.BASE_REWARDS_PER_EPOCH) * br_max
               + max_eff * finality_delay)
        penalty_parts.append(phase0_inactivity_kernel(
            xp, base_reward, eff, eligible, tgt_mask,
            base_rewards_per_epoch=int(spec.BASE_REWARDS_PER_EPOCH),
            proposer_reward_quotient=prq, finality_delay=finality_delay,
            inactivity_penalty_quotient=int(spec.INACTIVITY_PENALTY_QUOTIENT)))

    _guard(sum(int(r.max(initial=0)) for r in reward_parts),
           sum(int(p.max(initial=0)) for p in penalty_parts))
    rewards = reward_parts[0]
    for r in reward_parts[1:]:
        rewards = rewards + r
    penalties = penalty_parts[0]
    for p in penalty_parts[1:]:
        penalties = penalties + p

    balances = sa.balances()
    _guard(int(balances.max(initial=0)) + int(rewards.max(initial=0)))
    new_balances = apply_deltas_kernel(xp, balances, rewards, penalties)
    sa.set_balances(new_balances)


def _altair_participation(spec, sa, cols, flag_index, active_prev):
    """``get_unslashed_participating_indices`` as a mask (prev epoch)."""
    flags = sa.participation("previous")
    has_flag = (flags >> np.uint8(flag_index)) & np.uint8(1) == np.uint8(1)
    return active_prev & has_flag & ~cols["sl"]


def _altair_rewards_and_penalties(spec, state) -> None:
    """altair+ flag deltas + inactivity deltas, applied pairwise in spec
    order (each pair's decrease clamps at zero before the next applies)."""
    xp = np
    sa = state_arrays.of(state)
    cols = sa.registry()
    n = len(cols)
    if n == 0:
        return
    eff = cols["eff"]
    prev_epoch = int(spec.get_previous_epoch(state))
    active_prev, eligible = _epoch_masks(spec, cols, prev_epoch)
    total_balance = _total_active_balance(spec, cols,
                                          int(spec.get_current_epoch(state)))
    _guard(total_balance)
    increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    active_increments = total_balance // increment
    in_leak = bool(spec.is_in_inactivity_leak(state))
    weight_denominator = int(spec.WEIGHT_DENOMINATOR)
    brpi = increment * int(spec.BASE_REWARD_FACTOR) \
        // math.isqrt(total_balance)
    max_eff = int(eff.max(initial=0))
    _guard((max_eff // increment) * brpi)
    base_reward = (eff // np.uint64(increment)) * np.uint64(brpi)
    br_max = (max_eff // increment) * brpi

    head_flag = int(spec.TIMELY_HEAD_FLAG_INDEX)
    target_flag = int(spec.TIMELY_TARGET_FLAG_INDEX)
    delta_pairs = []
    target_participating = None
    for flag_index, weight in enumerate(spec.PARTICIPATION_FLAG_WEIGHTS):
        # cooperative deadline boundary: one check per flag component
        # (deadline_scope armed by try_process_rewards_and_penalties)
        supervisor.deadline_check()
        participating = _altair_participation(
            spec, sa, cols, flag_index, active_prev)
        if flag_index == target_flag:
            target_participating = participating
        up_balance = max(increment, _masked_sum(eff, participating))
        up_increments = up_balance // increment
        _guard(br_max * int(weight) * up_increments)
        delta_pairs.append(flag_deltas_kernel(
            xp, base_reward, eligible, participating,
            weight=int(weight), weight_denominator=weight_denominator,
            participating_increments=up_increments,
            active_increments=active_increments, in_leak=in_leak,
            is_head_flag=flag_index == head_flag))

    quotient = (int(spec.INACTIVITY_PENALTY_QUOTIENT_BELLATRIX)
                if "bellatrix" in _fork_lineage(spec)
                else int(spec.INACTIVITY_PENALTY_QUOTIENT_ALTAIR))
    # the store's view: includes the scores process_inactivity_updates
    # may have written earlier in this (possibly still uncommitted)
    # epoch transition — exactly what the spec loop would read from SSZ
    scores = sa.inactivity_scores()
    _guard(max_eff * int(scores.max(initial=0)))
    inactivity_penalties = inactivity_penalty_kernel(
        xp, eff, scores, eligible, target_participating,
        denominator=int(spec.config.INACTIVITY_SCORE_BIAS) * quotient)
    delta_pairs.append((np.zeros(n, dtype=np.uint64), inactivity_penalties))

    balances = sa.balances()
    max_bal = int(balances.max(initial=0))
    for rewards, penalties in delta_pairs:
        _guard(max_bal + int(rewards.max(initial=0)))
        balances = apply_deltas_kernel(xp, balances, rewards, penalties)
        max_bal = int(balances.max(initial=0))
    sa.set_balances(balances)


# ---------------------------------------------------------------------------
# process_inactivity_updates (altair+)
# ---------------------------------------------------------------------------

def _fast_inactivity_updates(spec, state) -> bool:
    from consensus_specs_tpu.parallel import mesh_epoch
    if mesh_epoch.try_inactivity_updates(spec, state):
        return True
    sa = state_arrays.of(state)
    cols = sa.registry()
    if len(cols) == 0:
        return False
    prev_epoch = int(spec.get_previous_epoch(state))
    active_prev, eligible = _epoch_masks(spec, cols, prev_epoch)
    participating = _altair_participation(
        spec, sa, cols, int(spec.TIMELY_TARGET_FLAG_INDEX), active_prev)
    scores = sa.inactivity_scores()
    bias = int(spec.config.INACTIVITY_SCORE_BIAS)
    _guard(int(scores.max(initial=0)) + bias)
    new_scores = inactivity_updates_kernel(
        np, scores, eligible, participating, bias=bias,
        recovery_rate=int(spec.config.INACTIVITY_SCORE_RECOVERY_RATE),
        in_leak=bool(spec.is_in_inactivity_leak(state)))
    sa.set_inactivity_scores(new_scores)
    return True


def try_process_inactivity_updates(spec, state) -> bool:
    if not enabled() or supervisor.probing():
        return _decline(state)
    if spec.get_current_epoch(state) == spec.GENESIS_EPOCH:
        _C_EPOCH_LOOP.add()
        return False    # spec body no-ops
    if "altair" not in _fork_lineage(spec):
        _C_EPOCH_LOOP.add()
        return False
    return _supervised(spec, state, "epoch.inactivity_updates",
                       "process_inactivity_updates",
                       _fast_inactivity_updates)


# ---------------------------------------------------------------------------
# process_registry_updates
# ---------------------------------------------------------------------------

def _fast_registry_updates(spec, state) -> bool:
    from consensus_specs_tpu.parallel import mesh_epoch
    if mesh_epoch.try_registry_updates(spec, state):
        return True
    _registry_updates(spec, state)
    return True


def try_process_registry_updates(spec, state) -> bool:
    if not enabled() or supervisor.probing():
        return _decline(state)
    return _supervised(spec, state, "epoch.registry_updates",
                       "process_registry_updates", _fast_registry_updates)


def _registry_updates(spec, state) -> None:
    """Eligibility scans and the activation-queue sort as array ops; the
    per-ejection exit-queue recurrence (a running max + churn counter) is
    simulated incrementally instead of re-scanning the registry per exit.

    Registry mutations run copy-on-write: the shared store columns are
    only copied (``registry_writable``) when this epoch actually stamps,
    ejects or activates someone — the common quiet epoch touches
    nothing.  SSZ per-index writes and column writes stay paired, then
    ``mark_registry_committed`` re-stamps the store."""
    sa = state_arrays.of(state)
    cols = sa.registry()
    n = len(cols)
    if n == 0:
        return
    current_epoch = int(spec.get_current_epoch(state))
    far_future = int(spec.FAR_FUTURE_EPOCH)
    max_eb = int(spec.MAX_EFFECTIVE_BALANCE)
    aee = cols["aee"]

    # cooperative deadline boundary before the eligibility scans
    # (deadline_scope armed by try_process_registry_updates)
    supervisor.deadline_check()
    # eligibility scans (the half the mesh engine runs shard-local on
    # the device mesh — parallel/mesh_epoch._p_registry_scan computes
    # these same facts as compact per-shard candidate index buffers and
    # funnels them into the shared _registry_apply_idx body below)
    queue_mask = (aee == np.uint64(far_future)) \
        & (cols["eff"] == np.uint64(max_eb))
    cur = np.uint64(current_epoch)
    active_cur = (cols["act"] <= cur) & (cur < cols["ext"])
    eject_mask = active_cur & (cols["eff"] <= np.uint64(
        int(spec.config.EJECTION_BALANCE)))
    # pending activations: stamped-this-epoch entries carry aee ==
    # current_epoch + 1 > finalized, and unstamped candidates carry
    # FAR_FUTURE_EPOCH — neither passes the finalized bound, so the
    # scan commutes with the stamping writes below
    eligible_mask = (aee <= np.uint64(
        int(state.finalized_checkpoint.epoch))) \
        & (cols["act"] == np.uint64(far_future))
    # explicit accumulator: a bool .sum() uses the platform default int,
    # which is 32-bit on some hosts — silently wrong above 2**31 lanes
    active_count = int(active_cur.sum(dtype=np.int64))
    _registry_apply(spec, state, sa, cols, queue_mask, eject_mask,
                    eligible_mask, active_count)


def _registry_apply(spec, state, sa, cols, queue_mask, eject_mask,
                    eligible_mask, active_count) -> None:
    """Mask-shaped entry into :func:`_registry_apply_idx` for the
    single-device engine: reduce the full-column eligibility masks to
    their (ascending) candidate index sets and resolve through the
    shared churn-ordered body."""
    _registry_apply_idx(spec, state, sa, cols,
                        np.nonzero(queue_mask)[0],
                        np.nonzero(eject_mask)[0],
                        np.nonzero(eligible_mask)[0],
                        active_count)


def _registry_apply_idx(spec, state, sa, cols, queue_idx, eject_idx,
                        eligible_idx, active_count) -> None:
    """Churn-ordered resolution of the registry scans: activation-queue
    stamps, the per-ejection exit-queue recurrence, and the
    (activation_eligibility_epoch, index)-sorted dequeue — shared by the
    single-device engine (via the :func:`_registry_apply` mask wrapper)
    and the mesh engine (whose shard-local scans hand their bounded,
    ascending candidate index sets straight here), so cross-shard
    ordering is byte-identical to the spec loop by construction.
    Candidate sets are bounded (registry churn, not registry size), so
    this body touches O(candidates) lanes — the two full-column ejection
    scans below are the documented exception, spec-required exact
    queue-state reads at the commit boundary."""
    validators = sequence_items(state.validators)
    current_epoch = int(spec.get_current_epoch(state))
    far_future = int(spec.FAR_FUTURE_EPOCH)

    wcols = None

    def writable():
        nonlocal wcols, cols
        if wcols is None:
            wcols = sa.registry_writable()
            cols = wcols
        return wcols

    aee = cols["aee"]

    # activation-queue eligibility stamps (is_eligible_for_activation_queue)
    stamp = current_epoch + 1
    if queue_idx.size:
        # copy-on-write BEFORE the paired SSZ writes: the generation
        # bump would otherwise read as a stale cell and re-extract
        aee = writable()["aee"]
        for i in queue_idx.tolist():
            validators[i].activation_eligibility_epoch = stamp
        aee[queue_idx] = np.uint64(stamp)

    # ejections: initiate_validator_exit per index, in index order.  The
    # churn limit is constant across the loop (assigned exit epochs are
    # all in the future, so current-epoch activity never changes).
    churn = max(int(spec.config.MIN_PER_EPOCH_CHURN_LIMIT),
                active_count // int(spec.config.CHURN_LIMIT_QUOTIENT))
    if eject_idx.size:
        ext = writable()["ext"]
        wd = wcols["wd"]
        # the exit-queue seed (max assigned exit epoch, and how much of
        # that epoch's churn is already spent) is a property of the FULL
        # exit column — a spec-required exact read, O(n) by nature, not
        # replaceable by a candidate gather (any validator may already
        # hold the max exit epoch)
        exited = ext[ext != np.uint64(far_future)]  # noqa: N1301
        queue_epoch = current_epoch + 1 + int(spec.MAX_SEED_LOOKAHEAD)
        if exited.size:
            queue_epoch = max(queue_epoch, int(exited.max()))
        qe = np.uint64(queue_epoch)
        queue_churn = int((ext == qe).sum(dtype=np.int64))  # noqa: N1301
        delay = int(spec.config.MIN_VALIDATOR_WITHDRAWABILITY_DELAY)
        _guard(queue_epoch + eject_idx.size + delay)
        for i in eject_idx.tolist():
            if int(ext[i]) != far_future:
                continue
            if queue_churn >= churn:
                queue_epoch += 1
                queue_churn = 0
            queue_churn += 1
            ext[i] = np.uint64(queue_epoch)
            wd[i] = np.uint64(queue_epoch + delay)
            validators[i].exit_epoch = queue_epoch
            validators[i].withdrawable_epoch = queue_epoch + delay

    # activations: sort eligibles by (activation_eligibility_epoch, index),
    # dequeue up to the (fork-dependent) activation churn limit
    idx = eligible_idx
    if idx.size:
        # re-read: the queue stamps above may have copied the column
        aee = cols["aee"]
        order = np.lexsort((idx, aee[idx]))
        activation_churn = churn
        if "deneb" in _fork_lineage(spec):
            activation_churn = min(
                int(spec.MAX_PER_EPOCH_ACTIVATION_CHURN_LIMIT), churn)
        take = idx[order][:activation_churn].tolist()
        if take:
            activation_epoch = current_epoch + 1 + int(spec.MAX_SEED_LOOKAHEAD)
            act = writable()["act"]
            for i in take:
                validators[i].activation_epoch = activation_epoch
                act[i] = np.uint64(activation_epoch)

    if wcols is not None:
        sa.mark_registry_committed()


# ---------------------------------------------------------------------------
# process_slashings
# ---------------------------------------------------------------------------

def _fast_slashings(spec, state) -> bool:
    lineage = _fork_lineage(spec)
    if "bellatrix" in lineage:
        multiplier = spec.PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX
    elif "altair" in lineage:
        multiplier = spec.PROPORTIONAL_SLASHING_MULTIPLIER_ALTAIR
    else:
        multiplier = spec.PROPORTIONAL_SLASHING_MULTIPLIER
    from consensus_specs_tpu.parallel import mesh_epoch
    if mesh_epoch.try_slashings(spec, state, int(multiplier)):
        return True
    _slashings(spec, state, int(multiplier))
    return True


def try_process_slashings(spec, state) -> bool:
    if not enabled() or supervisor.probing():
        return _decline(state)
    return _supervised(spec, state, "epoch.slashings",
                       "process_slashings", _fast_slashings)


def _slashings(spec, state, multiplier) -> None:
    sa = state_arrays.of(state)
    cols = sa.registry()
    if len(cols) == 0:
        return
    epoch = int(spec.get_current_epoch(state))
    total_balance = _total_active_balance(spec, cols, epoch)
    _guard(total_balance)
    slashed_sum = sum(int(s) for s in sequence_items(state.slashings))
    adjusted = min(slashed_sum * multiplier, total_balance)
    increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    target_epoch = epoch + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2
    _guard(target_epoch)
    target = cols["sl"] & (cols["wd"] == np.uint64(target_epoch))
    if not target.any():
        return
    _guard((int(cols["eff"].max(initial=0)) // increment) * adjusted)
    penalties = slashing_penalty_kernel(
        np, cols["eff"], target, increment=increment,
        adjusted_total_slashing_balance=adjusted, total_balance=total_balance)
    balances = sa.balances()
    new_balances = np.where(penalties > balances, np.uint64(0),
                            balances - penalties)
    sa.set_balances(new_balances)


# ---------------------------------------------------------------------------
# process_effective_balance_updates
# ---------------------------------------------------------------------------

def _fast_effective_balance_updates(spec, state) -> bool:
    from consensus_specs_tpu.parallel import mesh_epoch
    if mesh_epoch.try_effective_balance_updates(spec, state):
        return True
    _effective_balance_updates(spec, state)
    return True


def try_process_effective_balance_updates(spec, state) -> bool:
    if not enabled() or supervisor.probing():
        return _decline(state)
    return _supervised(spec, state, "epoch.effective_balance_updates",
                       "process_effective_balance_updates",
                       _fast_effective_balance_updates)


def _effective_balance_updates(spec, state) -> None:
    sa = state_arrays.of(state)
    cols = sa.registry()
    if len(cols) == 0:
        return
    increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    hysteresis_increment = increment // int(spec.HYSTERESIS_QUOTIENT)
    down = hysteresis_increment * int(spec.HYSTERESIS_DOWNWARD_MULTIPLIER)
    up = hysteresis_increment * int(spec.HYSTERESIS_UPWARD_MULTIPLIER)
    # the store's balances: includes this transition's still-deferred
    # reward/slashing writes, exactly what the spec loop would read
    balances = sa.balances()
    eff = cols["eff"]
    _guard(int(balances.max(initial=0)) + down, int(eff.max(initial=0)) + up)
    new_eff = effective_balance_kernel(
        np, balances, eff, increment=increment, downward_threshold=down,
        upward_threshold=up,
        max_effective_balance=int(spec.MAX_EFFECTIVE_BALANCE))
    changed = np.nonzero(eff != new_eff)[0]
    if changed.size == 0:
        return
    # copy-on-write BEFORE the paired SSZ writes (generation bump)
    sa.registry_writable()["eff"] = new_eff
    validators = sequence_items(state.validators)
    for i in changed.tolist():
        validators[i].effective_balance = int(new_eff[i])
    sa.mark_registry_committed()


# ---------------------------------------------------------------------------
# Spec-class routing
# ---------------------------------------------------------------------------

_TRY_BY_NAME = {
    "process_rewards_and_penalties": try_process_rewards_and_penalties,
    "process_inactivity_updates": try_process_inactivity_updates,
    "process_registry_updates": try_process_registry_updates,
    "process_slashings": try_process_slashings,
    "process_effective_balance_updates": try_process_effective_balance_updates,
}


def install_vectorized_epoch(cls) -> None:
    """Wrap a spec class's own epoch methods with the vectorized
    dispatch.  Applied to every fork class of both ladders by
    ``forks.install_engines``, so neither the hand-written bodies nor the
    ones emitted verbatim from the markdown carry a dispatch call.  Only
    methods defined on ``cls`` itself are wrapped (inherited ones are
    already wrapped on the base class), and wrapping is idempotent.

    ``process_epoch`` itself is additionally wrapped in the state-store
    commit scope (``state_arrays.commit_scope``) so the deferrable
    column writes of the whole transition flush to SSZ chunks once, at
    scope exit — unless the class opts out via
    ``_defer_epoch_commits = False`` (forks whose epoch ordering
    interleaves non-engine balance writes, e.g. custody_game)."""
    import functools
    for name, try_fn in _TRY_BY_NAME.items():
        fn = cls.__dict__.get(name)
        if fn is None or getattr(fn, "_vectorized_epoch_wrapper", False):
            continue

        def _make(orig, tfn):
            @functools.wraps(orig)
            def wrapper(self, state):
                if tfn(self, state):
                    return None
                return orig(self, state)
            wrapper._vectorized_epoch_wrapper = True
            return wrapper

        setattr(cls, name, _make(fn, try_fn))

    fn = cls.__dict__.get("process_epoch")
    if fn is not None and not getattr(fn, "_vectorized_epoch_wrapper", False) \
            and getattr(cls, "_defer_epoch_commits", True):
        @functools.wraps(fn)
        def epoch_wrapper(self, state, _orig=fn):
            with state_arrays.commit_scope(state):
                return _orig(self, state)
        epoch_wrapper._vectorized_epoch_wrapper = True
        setattr(cls, "process_epoch", epoch_wrapper)
