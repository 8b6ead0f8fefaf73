"""SPMD epoch transition over the validator mesh.

The PR-1 vectorized epoch engine (``ops/epoch_kernels.py``) re-expressed
the O(validators) epoch loops as columnar kernels against an ``xp``
array namespace — numpy on the host.  This module runs the SAME kernels
as ``shard_map`` SPMD programs over the 1-D ``validators`` mesh
(``mesh_state.build_mesh``): every device holds one shard of the store
columns (``mesh_state.sharded_cell``) and executes the per-shard
flag/reward/penalty kernels shard-locally; the only cross-device
traffic is ONE ``psum`` per sub-transition that needs a global sum
(:data:`PSUM_BUDGET` — the bench smoke counter-asserts it).

Byte-identity argument (the differential suites enforce it):

* elementwise uint64 lanes are identical under numpy and jax.numpy
  with 64-bit lanes enabled (``mesh_state.x64``) — same truncations,
  same clamps, and the kernels are literally shared with the
  single-device engine;
* the ``psum`` reductions are uint64 addition mod 2**64 — associative
  and commutative, so shard order cannot change the sum — and every
  reduction is guarded below 2**64 on the host before dispatch
  (conservative ``n * max`` bounds pre-reduction, the engine's exact
  bounds post-reduction), falling back to the single-device engine
  (which re-checks its own exact guards) instead of wrapping;
* ordering-sensitive registry churn (exit-queue recurrence,
  activation dequeue) is NOT distributed: the shard-local eligibility
  scans return COMPACT per-shard candidate index buffers (ascending by
  construction, O(S*cap) elements), and one shared ordered-resolution
  body (``epoch_kernels._registry_apply_idx``) applies them in spec
  order — the same code the single-device engine funnels its masks
  through, so cross-shard ordering is byte-identical to the spec loop
  by construction.

Host-work budget (speclint N13xx, ``speclint --cost-verdicts``;
docs/sharding.md): between dispatch and commit the host reads only
per-shard *partials* — the exact overflow-guard maxima ride back as
``(k, S)`` stacks (:func:`_p_shard_stats`), the active/attestation
balance sums as one psum vector, and the registry candidates as
bounded index buffers — never a per-epoch O(n) pass over the columns.
The ``mesh.host_partials`` counter is the runtime twin of that static
proof (``benchmarks/bench_mesh.py`` counter-asserts the per-epoch
total).

Dispatch layering: ``ops/epoch_kernels``'s ``_fast_*`` bodies offer each
sub-transition here first.  A decline (engine off, registry below the
``CS_TPU_MESH_MIN`` floor, a guard trip, an injected fault, a deadline)
falls back to the single-device columnar path — NOT the spec loop — so
the degradation ladder is mesh -> columnar -> spec, each leg
byte-identical.  The ``mesh.epoch`` faults site carries the full
harness contract: ``supervisor.admit`` gate, ``faults.check`` hook,
counted reason-labeled fallbacks, sentinel audits (host recomputation
of the same composition is authoritative — a corrupted device result
cannot commit past its audit), and the ``CS_TPU_MESH=0`` CI off-leg.
"""
import math

import numpy as np

from consensus_specs_tpu import faults, supervisor
from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.obs.tracing import span
from consensus_specs_tpu.parallel import mesh_state
from consensus_specs_tpu.state import arrays as state_arrays

SITE = "mesh.epoch"

# exact psum count per sub-transition: the collective budget the bench
# smoke asserts (one reduction program call == one psum, proven
# structurally by the jaxpr census in tests/test_mesh.py AND statically
# — before any device exists — by the speclint E1214 census over the
# dispatch bodies: `speclint . --effect-verdicts` prints the per-body
# proof lines; docs/static-analysis.md)
PSUM_BUDGET = {
    "rewards_and_penalties": 1,
    "inactivity_updates": 0,
    "registry_updates": 1,
    "slashings": 1,
    "effective_balance_updates": 0,
}

_C_MESH = obs_registry.counter("mesh.epoch").labels(path="mesh")
_C_PSUMS = {sub: obs_registry.counter("mesh.psums").labels(site=sub)
            for sub in PSUM_BUDGET}
_FALLBACKS = {
    "guard": obs_registry.counter(
        "mesh.epoch.fallbacks").labels(reason="guard"),
    "injected": obs_registry.counter(
        "mesh.epoch.fallbacks").labels(reason="injected"),
    "deadline": obs_registry.counter(
        "mesh.epoch.fallbacks").labels(reason="deadline"),
    "device_loss": obs_registry.counter(
        "mesh.epoch.fallbacks").labels(reason="device_loss"),
}
# host-side reads of per-shard partial stacks, in ELEMENTS (O(S) per
# reduction) — the runtime twin of the speclint N13xx host-work proof:
# between dispatch and commit the host touches partials, never O(n)
# columns (benchmarks/bench_mesh.py counter-asserts the per-epoch sum)
_C_PARTIALS = obs_registry.counter("mesh.host_partials").labels()
# a registry-scan candidate family outgrew the per-shard index cap:
# the dispatch declines and the columnar engine serves the call
_C_SCAN_OVERFLOW = obs_registry.counter("mesh.scan_overflow").labels()


def _ek():
    """The single-device engine (shared kernels + guard helpers).
    Imported lazily: ``epoch_kernels`` dispatches INTO this module, so a
    module-level import would be circular."""
    from consensus_specs_tpu.ops import epoch_kernels
    return epoch_kernels


# ---------------------------------------------------------------------------
# Compiled SPMD programs (memoized per mesh + static config)
# ---------------------------------------------------------------------------
#
# Scalars that vary per epoch (total balance, churn increments, brpi,
# epochs) ride in a replicated uint64 operand vector, NOT as python
# closure values — closing over them would recompile every epoch.
# Static arguments (fork constants, in_leak) key the program cache.

# speclint: cost: bounded: keyed per (kind, mesh, static fork config)
_PROGRAMS = {}


def _psum_u64(x):
    """``psum`` of uint64 partials, reduced as int64: the TPU lowers a
    sum all-reduce over s64 but not over u64 (v5e AOT compile, PR 21),
    and two's-complement addition mod 2**64 is bit-identical to
    unsigned.  Still ONE psum equation (the PSUM_BUDGET census)."""
    import jax
    import jax.numpy as jnp
    lax = jax.lax
    return lax.bitcast_convert_type(
        lax.psum(lax.bitcast_convert_type(x, jnp.int64), mesh_state.AXIS),
        jnp.uint64)


def _program(kind, mesh, static, builder):
    key = (kind, mesh, static)
    prog = _PROGRAMS.get(key)
    if prog is None:
        prog = builder()
        _PROGRAMS[key] = prog
    return prog


def _shard_specs(mesh, n_in, n_out, scalars=True):
    from jax.sharding import PartitionSpec as P
    axis = mesh_state.AXIS
    in_specs = tuple([P(axis)] * n_in + ([P()] if scalars else []))
    out_specs = tuple([P(axis)] * n_out) if n_out > 1 else P(axis)
    return in_specs, out_specs


def _altair_masks(jnp, act, ext, sl, part, prev, flag_index):
    """active-at-prev + per-flag unslashed-participating masks,
    shard-local (``_epoch_masks`` / ``_altair_participation``)."""
    active_prev = (act <= prev) & (prev < ext)
    has_flag = (part >> jnp.uint8(flag_index)) & jnp.uint8(1) \
        == jnp.uint8(1)
    return active_prev, active_prev & has_flag & ~sl


def _p_altair_sums(mesh, n_flags):
    """Reduction program: [total active balance, per-flag participating
    balances] — shard-local partials, ONE psum."""
    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map

        def local(eff, act, ext, sl, part, scal):
            prev, cur = scal[0], scal[1]
            zero = jnp.uint64(0)
            active_cur = (act <= cur) & (cur < ext)
            parts = [jnp.sum(jnp.where(active_cur, eff, zero),
                             dtype=jnp.uint64)]
            for f in range(n_flags):  # noqa: J203 (static: flag count)
                _, participating = _altair_masks(
                    jnp, act, ext, sl, part, prev, f)
                parts.append(jnp.sum(jnp.where(participating, eff, zero),
                                     dtype=jnp.uint64))
            return _psum_u64(jnp.stack(parts))

        in_specs, _ = _shard_specs(mesh, 5, 1)
        from jax.sharding import PartitionSpec as P
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=P()))
    return _program("altair_sums", mesh, (n_flags,), build)


def _p_masked_sums(mesh):
    """Generic reduction program: masked sums of one uint64 column under
    a stacked ``(k, n)`` mask operand — shard-local partials, ONE psum.
    The engine's sub-transitions now ride :func:`_p_active_sums` (which
    computes the active mask on device instead of taking a host-built
    column); this shape stays for the bench placement leg."""
    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local(eff, masks):
            parts = jnp.sum(
                jnp.where(masks, eff[None, :], jnp.uint64(0)),
                axis=1, dtype=jnp.uint64)
            return _psum_u64(parts)

        axis = mesh_state.AXIS
        return jax.jit(shard_map(
            local, mesh=mesh, in_specs=(P(axis), P(None, axis)),
            out_specs=P()))
    return _program("masked_sums", mesh, (), build)


def _p_active_sums(mesh, k):
    """Reduction program: [total active balance, per-mask attesting
    balances] with the active-at-current mask computed ON DEVICE from
    the ``act``/``ext`` columns — shard-local partials, ONE psum.
    Replaces the host-side ``active_cur`` elementwise pass the phase0
    and slashings bodies used to run (speclint N1301)."""
    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local(eff, act, ext, *rest):
            scal = rest[-1]
            cur = scal[0]
            zero = jnp.uint64(0)
            active_cur = (act <= cur) & (cur < ext)
            parts = [jnp.sum(jnp.where(active_cur, eff, zero),
                             dtype=jnp.uint64)]
            if k:
                masks = rest[0]
                for i in range(k):  # noqa: J203 (static: mask count)
                    parts.append(jnp.sum(
                        jnp.where(masks[i], eff, zero),
                        dtype=jnp.uint64))
            return _psum_u64(jnp.stack(parts))

        axis = mesh_state.AXIS
        in_specs = (P(axis), P(axis), P(axis)) \
            + ((P(None, axis),) if k else ()) + (P(),)
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=P()))
    return _program("active_sums", mesh, (k,), build)


def _p_shard_stats(mesh, k):
    """Per-shard maxima for the exact overflow-guard inputs: ``k``
    uint64 columns in, a ``(k, 1)`` stack of shard-local maxima out —
    ZERO collectives.  The host reduces the gathered ``(k, S)`` partial
    stack (:func:`_shard_maxes`) instead of re-scanning n-lane columns;
    pad lanes are zero, so the maxima match the host's
    ``max(initial=0)`` exactly."""
    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local(*cols):
            return jnp.stack([jnp.max(c) for c in cols])[:, None]

        axis = mesh_state.AXIS
        return jax.jit(shard_map(
            local, mesh=mesh, in_specs=tuple([P(axis)] * k),
            out_specs=P(None, axis)))
    return _program("shard_stats", mesh, (k,), build)


def _shard_maxes(mesh, *cols_dev):
    """Exact per-column maxima read off per-shard partials: the host
    reduces a ``(k, S)`` stack — O(S) elements, counted on
    ``mesh.host_partials`` — never the n-lane columns themselves
    (speclint N1301; docs/sharding.md host-work budget)."""
    parts = np.asarray(_p_shard_stats(mesh, len(cols_dev))(*cols_dev))
    _C_PARTIALS.add(parts.size)
    maxes = parts.max(axis=1)
    return [int(v) for v in maxes]


# inclusion-delay scan sentinel: an unbeatable (delay, ordinal) key —
# lanes no source attestation covers keep it, and the host only reads
# keys at covered lanes
_INCL_SENTINEL = (1 << 64) - 1


def _p_incl_scan(mesh):
    """Shard-local best-(delay, ordinal) scatter-min for the phase0
    inclusion-delay pass: the flat participant list rides replicated,
    each shard scatter-mins the entries that land in its own validator
    span, and — because every validator lane lives on exactly ONE
    shard — the per-validator minimum needs ZERO collectives, keeping
    the rewards_and_penalties psum budget at 1 (asserted structurally
    in tests/test_mesh.py)."""
    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local(anchor, idx, keys):
            n_local = anchor.shape[0]
            shard = jax.lax.axis_index(mesh_state.AXIS)
            li = idx - shard.astype(jnp.int64) * n_local
            ok = (li >= 0) & (li < n_local)
            li = jnp.where(ok, li, n_local)     # off-shard: dropped
            keys = jnp.where(ok, keys, jnp.uint64(_INCL_SENTINEL))
            base = jnp.full((n_local,), jnp.uint64(_INCL_SENTINEL),
                            dtype=jnp.uint64)
            return base.at[li].min(keys, mode="drop")

        axis = mesh_state.AXIS
        return jax.jit(shard_map(
            local, mesh=mesh, in_specs=(P(axis), P(), P()),
            out_specs=P(axis)))
    return _program("incl_scan", mesh, (), build)


def _p_altair_deltas(mesh, static):
    """Elementwise program: base rewards, the three flag delta pairs,
    the inactivity penalty pair, applied pairwise in spec order —
    shard-local, ZERO collectives."""
    (in_leak, weights, weight_denominator, increment, head_flag,
     target_flag) = static

    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        ek = _ek()

        # speclint: guarded-by-caller (_altair_rewards bounds
        # (max_eff // increment) * brpi and every flag product < 2**64
        # before dispatching this program)
        def local(eff, act, ext, sl, wd, part, scores, balances, scal):
            prev = scal[0]
            brpi = scal[1]
            active_increments = scal[2]
            inact_denom = scal[3]
            active_prev = (act <= prev) & (prev < ext)
            eligible = active_prev | (sl & (prev + jnp.uint64(1) < wd))
            base_reward = (eff // jnp.uint64(increment)) * brpi
            delta_pairs = []
            target_participating = None
            for f, weight in enumerate(weights):  # noqa: J203 (static)
                _, participating = _altair_masks(
                    jnp, act, ext, sl, part, prev, f)
                if f == target_flag:
                    target_participating = participating
                delta_pairs.append(ek.flag_deltas_kernel(
                    jnp, base_reward, eligible, participating,
                    weight=weight, weight_denominator=weight_denominator,
                    participating_increments=scal[4 + f],
                    active_increments=active_increments,
                    in_leak=in_leak, is_head_flag=f == head_flag))
            inact = ek.inactivity_penalty_kernel(
                jnp, eff, scores, eligible, target_participating,
                denominator=inact_denom)
            delta_pairs.append((jnp.zeros_like(inact), inact))
            out = balances
            for rewards, penalties in delta_pairs:
                out = ek.apply_deltas_kernel(jnp, out, rewards, penalties)
            return out

        in_specs, out_specs = _shard_specs(mesh, 8, 1)
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))
    return _program("altair_deltas", mesh, static, build)


def _p_phase0_deltas(mesh, static):
    """Elementwise program: phase0 base rewards, the three attestation
    component delta pairs, host-prepared inclusion rewards, the leak
    penalty — summed and applied once, matching the loop engine's
    accumulate-then-apply order.  Shard-local, ZERO collectives."""
    in_leak, brf, brpe, prq, ipq = static

    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        ek = _ek()

        # speclint: guarded-by-caller (_phase0_rewards bounds
        # max_eff * brf and every component product < 2**64 before
        # dispatching this program)
        def local(eff, act, ext, sl, wd, masks, incl_rewards, balances,
                  scal):
            prev = scal[0]
            sqrt_total = scal[1]
            total_increments = scal[2]
            finality_delay = scal[3]
            active_prev = (act <= prev) & (prev < ext)
            eligible = active_prev | (sl & (prev + jnp.uint64(1) < wd))
            base_reward = (eff * jnp.uint64(brf)) // sqrt_total \
                // jnp.uint64(brpe)
            rewards = incl_rewards
            penalties = jnp.zeros_like(incl_rewards)
            for i in range(3):  # noqa: J203 (static: src/tgt/head)
                r, p = ek.phase0_component_kernel(
                    jnp, base_reward, eligible, masks[i],
                    in_leak=in_leak, attesting_increments=scal[4 + i],
                    total_increments=total_increments)
                rewards = rewards + r
                penalties = penalties + p
            if in_leak:
                penalties = penalties + ek.phase0_inactivity_kernel(
                    jnp, base_reward, eff, eligible, masks[1],
                    base_rewards_per_epoch=brpe,
                    proposer_reward_quotient=prq,
                    finality_delay=finality_delay,
                    inactivity_penalty_quotient=ipq)
            return ek.apply_deltas_kernel(jnp, balances, rewards,
                                          penalties)

        import jax
        from jax.sharding import PartitionSpec as P
        axis = mesh_state.AXIS
        in_specs = (P(axis), P(axis), P(axis), P(axis), P(axis),
                    P(None, axis), P(axis), P(axis), P())
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(axis)))
    return _program("phase0_deltas", mesh, static, build)


def _p_inactivity(mesh, static):
    """Elementwise program for ``process_inactivity_updates`` —
    shard-local, ZERO collectives."""
    bias, recovery_rate, in_leak, target_flag = static

    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        ek = _ek()

        def local(act, ext, sl, wd, part, scores, scal):
            prev = scal[0]
            active_prev, participating = _altair_masks(
                jnp, act, ext, sl, part, prev, target_flag)
            eligible = active_prev | (sl & (prev + jnp.uint64(1) < wd))
            return ek.inactivity_updates_kernel(
                jnp, scores, eligible, participating, bias=bias,
                recovery_rate=recovery_rate, in_leak=in_leak)

        in_specs, out_specs = _shard_specs(mesh, 6, 1)
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))
    return _program("inactivity", mesh, static, build)


def _p_slashings(mesh, static):
    """Elementwise program for ``process_slashings`` penalties + clamped
    application — shard-local, ZERO collectives (the total-balance
    reduction runs through :func:`_p_masked_sums`)."""
    increment, = static

    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        ek = _ek()

        def local(eff, sl, wd, balances, scal):
            adjusted, total_balance, target_epoch = \
                scal[0], scal[1], scal[2]
            target = sl & (wd == target_epoch)
            penalties = ek.slashing_penalty_kernel(
                jnp, eff, target, increment=increment,
                adjusted_total_slashing_balance=adjusted,
                total_balance=total_balance)
            return jnp.where(penalties > balances, jnp.uint64(0),
                             balances - penalties)

        in_specs, out_specs = _shard_specs(mesh, 4, 1)
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))
    return _program("slashings", mesh, static, build)


def _p_eff_balance(mesh, static):
    """Elementwise program for the effective-balance hysteresis —
    shard-local, ZERO collectives."""
    increment, down, up, max_eb = static

    def build():
        import jax
        from jax import shard_map
        ek = _ek()

        def local(balances, eff):
            import jax.numpy as jnp
            return ek.effective_balance_kernel(
                jnp, balances, eff, increment=increment,
                downward_threshold=down, upward_threshold=up,
                max_effective_balance=max_eb)

        in_specs, out_specs = _shard_specs(mesh, 2, 1, scalars=False)
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))
    return _program("eff_balance", mesh, static, build)


# per-shard candidate index cap for the registry eligibility scans: a
# shard whose candidate family outgrows this declines the dispatch (the
# columnar engine serves the call) rather than truncating — real epochs
# churn a handful of validators per family, so 256 never binds in the
# differential suites while keeping the host read O(S * cap)
_SCAN_CAP = 256


def _p_registry_scan(mesh, static):
    """Registry eligibility scans, shard-local: activation-queue stamps,
    ejection candidates, dequeue eligibles — plus the active-set count
    for the churn limit (the sub-transition's ONE psum).  Each family
    comes back as a COMPACT per-shard index buffer (``cap`` slots per
    shard, global indices, ascending within a shard) plus the true
    per-shard candidate counts: the host concatenates count-sliced
    spans (:func:`_gather_idx`) and resolves the churn-ordered queues
    through the shared ``epoch_kernels._registry_apply_idx`` body —
    O(S*cap) elements read, never the n-lane masks.  Pad lanes can
    never be candidates (``aee``/``act``/``ext`` pad to zero, so every
    family predicate is False there)."""
    far, max_eb, ejection, cap = static

    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local(aee, act, ext, eff, scal):
            cur, finalized = scal[0], scal[1]
            n_local = aee.shape[0]
            shard = jax.lax.axis_index(mesh_state.AXIS)
            base = shard.astype(jnp.int64) * n_local
            queue_mask = (aee == jnp.uint64(far)) \
                & (eff == jnp.uint64(max_eb))
            active_cur = (act <= cur) & (cur < ext)
            eject_mask = active_cur & (eff <= jnp.uint64(ejection))
            eligible_mask = (aee <= finalized) & (act == jnp.uint64(far))
            bufs, counts = [], []
            families = (queue_mask, eject_mask, eligible_mask)
            for mask in families:  # noqa: J203 (static: 3 families)
                li = jnp.nonzero(mask, size=cap, fill_value=n_local)[0]
                bufs.append(base + li.astype(jnp.int64))
                counts.append(jnp.sum(mask, dtype=jnp.int64))
            count = jax.lax.psum(
                jnp.sum(active_cur, dtype=jnp.int64)[None],
                mesh_state.AXIS)
            return (bufs[0], bufs[1], bufs[2],
                    jnp.stack(counts)[:, None], count)

        axis = mesh_state.AXIS
        return jax.jit(shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
            out_specs=(P(axis), P(axis), P(axis), P(None, axis), P())))
    return _program("registry_scan", mesh, static, build)


def _gather_idx(buf, counts, cap):
    """Concatenate each shard's first ``counts[s]`` candidates out of
    its ``cap``-slot span of ``buf``.  Per-shard ascending
    (``jnp.nonzero``) and shard spans ascending, so the result is
    globally ascending — byte-identical to ``np.nonzero`` over the
    unsharded mask column."""
    spans = [buf[s * cap:s * cap + int(c)] for s, c in enumerate(counts)]
    if not spans:
        return np.zeros(0, dtype=np.int64)
    return np.ascontiguousarray(np.concatenate(spans))


# ---------------------------------------------------------------------------
# Supervised dispatch (site mesh.epoch; falls back to the single-device
# columnar engine, which re-checks its own exact guards)
# ---------------------------------------------------------------------------

def _dispatch(spec, state, sub, fast_fn) -> bool:
    """Run one sub-transition through the mesh.  True: the mesh computed
    and committed the columns (the caller's single-device body must not
    run).  False: declined/failed — the caller proceeds single-device."""
    if supervisor.probing() or not mesh_state.enabled():
        return False
    sa = state_arrays.of(state)
    if not mesh_state.engaged(len(sa.registry())):
        return False
    if not supervisor.admit(SITE):
        return False
    ek = _ek()
    checked = False
    while True:
        try:
            if not checked:
                faults.check(SITE)
                checked = True
            with supervisor.deadline_scope(SITE):
                with span("mesh.epoch.dispatch"):
                    with mesh_state.x64():
                        if faults.loss_armed(SITE):
                            raise mesh_state.DeviceLoss(SITE)
                        handled = fast_fn(spec, state, sa)
        except mesh_state.DeviceLoss:
            # a device dropped out mid-dispatch: retire every cached
            # placement, re-shard over the survivors, book the counted
            # fallback and retry elastically — unless the survivor
            # count falls below the two-device gate / engagement floor,
            # in which case the single-device engine serves the call
            mesh_state.lose_device(SITE)
            faults.count_fallback(_FALLBACKS, None, organic="device_loss",
                                  site=SITE)
            if mesh_state.enabled() \
                    and mesh_state.engaged(len(sa.registry())):
                continue
            return False
        except ek._Fallback:
            faults.count_fallback(_FALLBACKS, None, organic="guard",
                                  site=SITE)
            return False
        except (faults.InjectedFault, supervisor.DeadlineExceeded) as exc:
            faults.count_fallback(_FALLBACKS, exc, site=SITE)
            return False
        break
    if not handled:
        return False
    supervisor.note_success(SITE)
    _C_MESH.add()
    return True


def _finish_column(result: np.ndarray, host_recompute) -> np.ndarray:
    """Corrupt hook + sentinel audit for one device-computed column.
    ``host_recompute`` replays the SAME composition with numpy kernels
    and host-exact reductions; on an audit its answer is authoritative,
    so a silently-wrong device result cannot commit past its audit."""
    if faults.corrupt_armed(SITE):
        # silent-corruption injection (sentinel-audit test vector)
        result = result.copy()
        if result.size:
            result[0] ^= result.dtype.type(1)
    if supervisor.audit_due(SITE):
        golden = host_recompute()
        ok = bool(np.array_equal(result, golden))
        supervisor.audit_result(
            SITE, ok, "mesh SPMD column diverged from the host "
            "recomputation of the same kernels")
        return golden
    return result


def _columns(sa, mesh):
    reg = mesh_state.sharded_cell(sa, "registry", mesh)
    return reg


def _scal(values) -> np.ndarray:
    return np.array([int(v) for v in values], dtype=np.uint64)


# ---------------------------------------------------------------------------
# Sub-transition entry points (called by ops/epoch_kernels._fast_*)
# ---------------------------------------------------------------------------

# speclint: cost: O(S)
def try_rewards_and_penalties(spec, state) -> bool:
    def fast(spec, state, sa):
        ek = _ek()
        if "altair" in ek._fork_lineage(spec):
            return _altair_rewards(spec, state, sa)
        return _phase0_rewards(spec, state, sa)
    return _dispatch(spec, state, "rewards_and_penalties", fast)


def _altair_rewards(spec, state, sa) -> bool:
    ek = _ek()
    cols = sa.registry()
    n = len(cols)
    if n == 0:
        return False
    mesh = mesh_state.build_mesh()
    eff = cols["eff"]
    reg = _columns(sa, mesh)
    part = mesh_state.sharded_cell(sa, "participation_previous", mesh)
    sc_dev = mesh_state.sharded_cell(sa, "inactivity_scores", mesh)
    bal_dev = mesh_state.sharded_cell(sa, "balances", mesh)
    # exact guard inputs off per-shard max partials — the host never
    # re-scans the n-lane columns (speclint N1301; host-work budget)
    max_eff, max_score, max_bal = _shard_maxes(
        mesh, reg["eff"], sc_dev, bal_dev)
    # pre-reduction conservative bound: every psum lane sum is <= n *
    # max_eff, so < 2**64 here implies the device reduction is exact
    ek._guard(n * max_eff)
    prev_epoch = int(spec.get_previous_epoch(state))
    cur_epoch = int(spec.get_current_epoch(state))
    increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    weights = tuple(int(w) for w in spec.PARTICIPATION_FLAG_WEIGHTS)
    sums_prog = _p_altair_sums(mesh, len(weights))
    _C_PSUMS["rewards_and_penalties"].add()
    sums = np.asarray(sums_prog(
        reg["eff"], reg["act"], reg["ext"], reg["sl"], part,
        mesh_state.replicate(_scal([prev_epoch, cur_epoch]), mesh)))
    total_balance = max(increment, int(sums[0]))
    up_balances = [max(increment, int(s)) for s in sums[1:]]
    # from here the guard set is EXACTLY the single-device engine's
    ek._guard(total_balance)
    active_increments = total_balance // increment
    in_leak = bool(spec.is_in_inactivity_leak(state))
    weight_denominator = int(spec.WEIGHT_DENOMINATOR)
    brpi = increment * int(spec.BASE_REWARD_FACTOR) \
        // math.isqrt(total_balance)
    ek._guard((max_eff // increment) * brpi)
    br_max = (max_eff // increment) * brpi
    up_increments = []
    for w, ub in zip(weights, up_balances):
        ui = ub // increment
        ek._guard(br_max * w * ui)
        up_increments.append(ui)
    quotient = (int(spec.INACTIVITY_PENALTY_QUOTIENT_BELLATRIX)
                if "bellatrix" in ek._fork_lineage(spec)
                else int(spec.INACTIVITY_PENALTY_QUOTIENT_ALTAIR))
    inact_denom = int(spec.config.INACTIVITY_SCORE_BIAS) * quotient
    scores = sa.inactivity_scores()
    ek._guard(max_eff * max_score)
    balances = sa.balances()
    # pairwise application bound: each pair adds at most one flag
    # reward (or the zero inactivity reward) on top of the running max
    ek._guard(max_bal + (len(weights) + 1) * br_max)
    static = (in_leak, weights, weight_denominator, increment,
              int(spec.TIMELY_HEAD_FLAG_INDEX),
              int(spec.TIMELY_TARGET_FLAG_INDEX))
    prog = _p_altair_deltas(mesh, static)
    scal = _scal([prev_epoch, brpi, active_increments, inact_denom]
                 + up_increments)
    out = mesh_state.unshard(
        prog(reg["eff"], reg["act"], reg["ext"], reg["sl"], reg["wd"],
             part, sc_dev, bal_dev, mesh_state.replicate(scal, mesh)), n)

    # speclint: guarded-by-caller (_altair_rewards bounds the same
    # products before the audit closure can run)
    def host_recompute():
        active_prev, eligible = ek._epoch_masks(spec, cols, prev_epoch)
        base_reward = (eff // np.uint64(increment)) * np.uint64(brpi)
        acc = balances
        target_participating = None
        for f, w in enumerate(weights):
            participating = ek._altair_participation(
                spec, sa, cols, f, active_prev)
            if f == static[5]:
                target_participating = participating
            r, p = ek.flag_deltas_kernel(
                np, base_reward, eligible, participating, weight=w,
                weight_denominator=weight_denominator,
                participating_increments=up_increments[f],
                active_increments=active_increments, in_leak=in_leak,
                is_head_flag=f == static[4])
            acc = ek.apply_deltas_kernel(np, acc, r, p)
        inact = ek.inactivity_penalty_kernel(
            np, eff, scores, eligible, target_participating,
            denominator=inact_denom)
        return ek.apply_deltas_kernel(
            np, acc, np.zeros(n, dtype=np.uint64), inact)

    sa.set_balances(_finish_column(out, host_recompute))
    return True


def _phase0_rewards(spec, state, sa) -> bool:
    ek = _ek()
    cols = sa.registry()
    n = len(cols)
    if n == 0:
        return False
    mesh = mesh_state.build_mesh()
    # spec helpers up front: assertion behavior (exception as
    # invalidity) must fire exactly as in the loop path
    prev_epoch = spec.get_previous_epoch(state)
    src_atts = spec.get_matching_source_attestations(state, prev_epoch)
    tgt_atts = spec.get_matching_target_attestations(state, prev_epoch)
    head_atts = spec.get_matching_head_attestations(state, prev_epoch)
    src_set = spec.get_unslashed_attesting_indices(state, src_atts)
    tgt_set = spec.get_unslashed_attesting_indices(state, tgt_atts)
    head_set = spec.get_unslashed_attesting_indices(state, head_atts)
    prev_epoch = int(prev_epoch)
    cur_epoch = int(spec.get_current_epoch(state))
    eff = cols["eff"]
    reg = _columns(sa, mesh)
    bal_dev = mesh_state.sharded_cell(sa, "balances", mesh)
    # exact guard inputs off per-shard max partials — the host never
    # re-scans the n-lane columns (speclint N1301; host-work budget)
    max_eff, max_bal = _shard_maxes(mesh, reg["eff"], bal_dev)
    ek._guard(n * max_eff)
    increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    att_masks = np.stack([ek._mask_from_indices(n, s)
                          for s in (src_set, tgt_set, head_set)])
    sums_prog = _p_active_sums(mesh, 3)
    _C_PSUMS["rewards_and_penalties"].add()
    sums = np.asarray(sums_prog(
        reg["eff"], reg["act"], reg["ext"],
        _place_masks(att_masks, mesh),
        mesh_state.replicate(_scal([cur_epoch]), mesh)))
    total_balance = max(increment, int(sums[0]))
    ek._guard(total_balance)
    total_increments = total_balance // increment
    in_leak = bool(spec.is_in_inactivity_leak(state))
    sqrt_total = int(spec.integer_squareroot(total_balance))
    brf = int(spec.BASE_REWARD_FACTOR)
    brpe = int(spec.BASE_REWARDS_PER_EPOCH)
    ek._guard(max_eff * brf)
    br_max = max_eff * brf // sqrt_total // brpe
    att_increments = []
    for s in sums[1:]:
        ai = max(increment, int(s)) // increment
        ek._guard(br_max * ai)
        att_increments.append(ai)

    # inclusion-delay rewards: the best-delay/proposer scan runs
    # SHARD-LOCAL on the mesh.  The flat participant list (one entry
    # per (attestation, attester)) folds each entry into ONE uint64 key
    # `delay << 32 | attestation ordinal`, whose lexicographic minimum
    # reproduces the spec loop's ordered strict-< update byte-for-byte
    # (the FIRST attestation at the minimal delay wins — ties break on
    # the ordinal); the proposer-reward apply below stays on the host
    # in spec order.  Flat operands pad to a power of two so the scan
    # program compiles O(log flats) shapes, not one per epoch.
    # speclint: invariant: prq >= 1
    prq = int(spec.PROPOSER_REWARD_QUOTIENT)
    flat_idx, flat_key, att_proposers = [], [], []
    for ordinal, att in enumerate(src_atts):
        att_proposers.append(int(att.proposer_index))
        idxs = spec.get_attesting_indices(state, att.data,
                                          att.aggregation_bits)
        if not idxs:
            continue
        ii = np.fromiter(idxs, dtype=np.int64, count=len(idxs))
        flat_idx.append(ii)
        flat_key.append(np.full(
            ii.size, np.uint64((int(att.inclusion_delay) << 32)
                               | ordinal), dtype=np.uint64))
    best_key = None
    if flat_idx:
        idx = np.concatenate(flat_idx)
        keys = np.concatenate(flat_key)
        pad = (1 << max(1, (idx.size - 1).bit_length())) - idx.size
        if pad:
            idx = np.concatenate(
                [idx, np.full(pad, -1, dtype=np.int64)])
            keys = np.concatenate(
                [keys, np.full(pad, _INCL_SENTINEL, dtype=np.uint64)])
        best_key = mesh_state.unshard(
            _p_incl_scan(mesh)(reg["eff"],
                               mesh_state.replicate(idx, mesh),
                               mesh_state.replicate(keys, mesh)), n)
    # the source-attester candidate set is BOUNDED (the spec sets are
    # already materialized) — gather the candidate lanes first and run
    # the base/proposer-reward arithmetic on O(candidates) elements,
    # never on full columns (speclint N1302); every source attester is
    # covered by some source attestation, so reading the scatter-min
    # keys only at those lanes is byte-identical to the masked update
    src_idx = np.fromiter(sorted(src_set), dtype=np.int64,
                          count=len(src_set))
    incl_rewards = np.zeros(n, dtype=np.uint64)
    incl_max = 0
    if src_idx.size:
        if best_key is None:
            delay_src = np.full(src_idx.size, (1 << 64) - 1,
                                dtype=np.uint64)
            prop_src = np.zeros(src_idx.size, dtype=np.int64)
        else:
            key_src = best_key[src_idx]
            delay_src = key_src >> np.uint64(32)
            prop_src = np.array(att_proposers, dtype=np.int64)[
                (key_src & np.uint64(0xFFFFFFFF)).astype(np.int64)]
        eff_src = eff[src_idx]
        base_src = (eff_src * np.uint64(brf)) // np.uint64(sqrt_total) \
            // np.uint64(brpe)
        # safe under the prq >= 1 invariant: proposer_reward <=
        # base_reward, preserved under the shared index (the U9xx
        # prover certifies the same line in the single-device engine)
        proposer_src = base_src // np.uint64(prq)
        max_attester = base_src - proposer_src
        incl_rewards[src_idx] = max_attester // delay_src
        ek._guard(br_max + src_idx.size * (br_max // prq))
        np.add.at(incl_rewards, prop_src, proposer_src)
        # incl_rewards is zero off the touched lanes, so the bounded
        # gather max equals the full-column max the guard needs
        touched = np.union1d(src_idx, prop_src)
        incl_max = int(incl_rewards[touched].max(initial=0))

    finality_delay = int(spec.get_finality_delay(state)) if in_leak else 0
    ipq = int(spec.INACTIVITY_PENALTY_QUOTIENT)
    if in_leak:
        ek._guard(brpe * br_max + max_eff * finality_delay)
    # accumulate-then-apply bound, conservative over the exact per-part
    # maxima the single-device engine reads off its materialized parts
    balances = sa.balances()
    ek._guard(3 * br_max + incl_max + max_bal,
              3 * br_max + brpe * br_max + max_eff * finality_delay)
    static = (in_leak, brf, brpe, prq, ipq)
    prog = _p_phase0_deltas(mesh, static)
    scal = _scal([prev_epoch, sqrt_total, total_increments,
                  finality_delay] + att_increments)
    out = mesh_state.unshard(
        prog(reg["eff"], reg["act"], reg["ext"], reg["sl"], reg["wd"],
             _place_masks(att_masks, mesh),
             mesh_state.place(incl_rewards, mesh), bal_dev,
             mesh_state.replicate(scal, mesh)), n)

    def host_recompute():
        _, eligible = ek._epoch_masks(spec, cols, prev_epoch)
        # full-column base/proposer rewards: the audit recomputation is
        # deliberately independent of the bounded candidate gathers it
        # is auditing (exempt from the host-work budget by design)
        ek._guard(max_eff * brf)
        base_reward = (eff * np.uint64(brf)) // np.uint64(sqrt_total) \
            // np.uint64(brpe)
        # the inclusion-delay scan recomputes through the SPEC-SHAPED
        # per-attestation loop — the audit must be independent of the
        # sharded scatter-min it is auditing
        h_delay = np.full(n, (1 << 64) - 1, dtype=np.uint64)
        h_proposer = np.zeros(n, dtype=np.int64)
        for att in src_atts:
            idxs = spec.get_attesting_indices(state, att.data,
                                              att.aggregation_bits)
            if not idxs:
                continue
            ii = np.fromiter(idxs, dtype=np.int64, count=len(idxs))
            upd = np.uint64(int(att.inclusion_delay)) < h_delay[ii]
            sel = ii[upd]
            h_delay[sel] = np.uint64(int(att.inclusion_delay))
            h_proposer[sel] = int(att.proposer_index)
        rewards = np.zeros(n, dtype=np.uint64)
        if src_idx.size:
            # speclint: invariant: prq >= 1
            base_src_h = base_reward[src_idx]
            proposer_src_h = base_src_h // np.uint64(prq)
            max_attester = base_src_h - proposer_src_h
            rewards[src_idx] = max_attester // h_delay[src_idx]
            np.add.at(rewards, h_proposer[src_idx], proposer_src_h)
        penalties = np.zeros(n, dtype=np.uint64)
        for i in range(3):
            r, p = ek.phase0_component_kernel(
                np, base_reward, eligible, att_masks[i],
                in_leak=in_leak, attesting_increments=att_increments[i],
                total_increments=total_increments)
            rewards = rewards + r
            penalties = penalties + p
        if in_leak:
            penalties = penalties + ek.phase0_inactivity_kernel(
                np, base_reward, eff, eligible, att_masks[1],
                base_rewards_per_epoch=brpe,
                proposer_reward_quotient=prq,
                finality_delay=finality_delay,
                inactivity_penalty_quotient=ipq)
        return ek.apply_deltas_kernel(np, balances, rewards, penalties)

    sa.set_balances(_finish_column(out, host_recompute))
    return True


def _place_masks(masks: np.ndarray, mesh):
    """Place a stacked ``(k, n)`` bool mask with the VALIDATOR axis
    (axis 1) sharded — pad lanes False, so they drop out of every
    reduction and delta."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    pad = mesh_state.pad_amount(masks.shape[1], mesh.shape[mesh_state.AXIS])
    if pad:
        masks = np.concatenate(
            [masks, np.zeros((masks.shape[0], pad), dtype=bool)], axis=1)
    return jax.device_put(
        masks, NamedSharding(mesh, P(None, mesh_state.AXIS)))


# speclint: cost: O(S)
def try_inactivity_updates(spec, state) -> bool:
    def fast(spec, state, sa):
        ek = _ek()
        cols = sa.registry()
        n = len(cols)
        if n == 0:
            return False
        mesh = mesh_state.build_mesh()
        scores = sa.inactivity_scores()
        reg = _columns(sa, mesh)
        part = mesh_state.sharded_cell(sa, "participation_previous", mesh)
        sc_dev = mesh_state.sharded_cell(sa, "inactivity_scores", mesh)
        max_score, = _shard_maxes(mesh, sc_dev)
        bias = int(spec.config.INACTIVITY_SCORE_BIAS)
        ek._guard(max_score + bias)
        prev_epoch = int(spec.get_previous_epoch(state))
        in_leak = bool(spec.is_in_inactivity_leak(state))
        static = (bias, int(spec.config.INACTIVITY_SCORE_RECOVERY_RATE),
                  in_leak, int(spec.TIMELY_TARGET_FLAG_INDEX))
        prog = _p_inactivity(mesh, static)
        out = mesh_state.unshard(
            prog(reg["act"], reg["ext"], reg["sl"], reg["wd"], part,
                 sc_dev,
                 mesh_state.replicate(_scal([prev_epoch]), mesh)), n)

        def host_recompute():
            active_prev, eligible = ek._epoch_masks(spec, cols,
                                                    prev_epoch)
            participating = ek._altair_participation(
                spec, sa, cols, static[3], active_prev)
            return ek.inactivity_updates_kernel(
                np, scores, eligible, participating, bias=bias,
                recovery_rate=static[1], in_leak=in_leak)

        sa.set_inactivity_scores(_finish_column(out, host_recompute))
        return True
    return _dispatch(spec, state, "inactivity_updates", fast)


# speclint: cost: O(S)
def try_slashings(spec, state, multiplier: int) -> bool:
    def fast(spec, state, sa):
        ek = _ek()
        from consensus_specs_tpu.utils.ssz import sequence_items
        cols = sa.registry()
        n = len(cols)
        if n == 0:
            return False
        mesh = mesh_state.build_mesh()
        eff = cols["eff"]
        reg = _columns(sa, mesh)
        max_eff, = _shard_maxes(mesh, reg["eff"])
        ek._guard(n * max_eff)
        epoch = int(spec.get_current_epoch(state))
        _C_PSUMS["slashings"].add()
        # the active-at-current mask lives ON DEVICE (k=0: no extra
        # mask rows) — the host reads one psum'd sum, not a column
        sums = np.asarray(_p_active_sums(mesh, 0)(
            reg["eff"], reg["act"], reg["ext"],
            mesh_state.replicate(_scal([epoch]), mesh)))
        total_balance = max(int(spec.EFFECTIVE_BALANCE_INCREMENT),
                            int(sums[0]))
        ek._guard(total_balance)
        slashed_sum = sum(int(s) for s in sequence_items(state.slashings))
        adjusted = min(slashed_sum * multiplier, total_balance)
        increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
        target_epoch = epoch + int(spec.EPOCHS_PER_SLASHINGS_VECTOR) // 2
        ek._guard(target_epoch)
        ek._guard((max_eff // increment) * adjusted)
        balances = sa.balances()
        bal_dev = mesh_state.sharded_cell(sa, "balances", mesh)
        prog = _p_slashings(mesh, (increment,))
        scal = _scal([adjusted, total_balance, target_epoch])
        out = mesh_state.unshard(
            prog(reg["eff"], reg["sl"], reg["wd"], bal_dev,
                 mesh_state.replicate(scal, mesh)), n)

        def host_recompute():
            target = cols["sl"] & (cols["wd"] == np.uint64(target_epoch))
            penalties = ek.slashing_penalty_kernel(
                np, eff, target, increment=increment,
                adjusted_total_slashing_balance=adjusted,
                total_balance=total_balance)
            return np.where(penalties > balances, np.uint64(0),
                            balances - penalties)

        sa.set_balances(_finish_column(out, host_recompute))
        return True
    return _dispatch(spec, state, "slashings", fast)


# speclint: cost: O(S)
def try_effective_balance_updates(spec, state) -> bool:
    def fast(spec, state, sa):
        ek = _ek()
        from consensus_specs_tpu.utils.ssz import sequence_items
        cols = sa.registry()
        n = len(cols)
        if n == 0:
            return False
        mesh = mesh_state.build_mesh()
        increment = int(spec.EFFECTIVE_BALANCE_INCREMENT)
        hysteresis_increment = increment // int(spec.HYSTERESIS_QUOTIENT)
        down = hysteresis_increment \
            * int(spec.HYSTERESIS_DOWNWARD_MULTIPLIER)
        up = hysteresis_increment * int(spec.HYSTERESIS_UPWARD_MULTIPLIER)
        balances = sa.balances()
        eff = cols["eff"]
        reg = _columns(sa, mesh)
        bal_dev = mesh_state.sharded_cell(sa, "balances", mesh)
        max_bal, max_eff = _shard_maxes(mesh, bal_dev, reg["eff"])
        ek._guard(max_bal + down, max_eff + up)
        static = (increment, down, up, int(spec.MAX_EFFECTIVE_BALANCE))
        prog = _p_eff_balance(mesh, static)
        new_eff = mesh_state.unshard(prog(bal_dev, reg["eff"]), n)

        def host_recompute():
            return ek.effective_balance_kernel(
                np, balances, eff, increment=increment,
                downward_threshold=down, upward_threshold=up,
                max_effective_balance=static[3])

        new_eff = _finish_column(new_eff, host_recompute)
        # the commit diff IS the SSZ write-back boundary: the paired
        # per-index writes need the changed lanes whichever engine ran
        changed = np.nonzero(eff != new_eff)[0]  # noqa: N1301
        if changed.size == 0:
            return True
        # copy-on-write BEFORE the paired SSZ writes (generation bump) —
        # the same write protocol as the single-device engine
        sa.registry_writable()["eff"] = new_eff
        validators = sequence_items(state.validators)
        for i in changed.tolist():
            validators[i].effective_balance = int(new_eff[i])
        sa.mark_registry_committed()
        return True
    return _dispatch(spec, state, "effective_balance_updates", fast)


# speclint: cost: O(S)
def try_registry_updates(spec, state) -> bool:
    def fast(spec, state, sa):
        ek = _ek()
        cols = sa.registry()
        n = len(cols)
        if n == 0:
            return False
        mesh = mesh_state.build_mesh()
        current_epoch = int(spec.get_current_epoch(state))
        finalized = int(state.finalized_checkpoint.epoch)
        static = (int(spec.FAR_FUTURE_EPOCH),
                  int(spec.MAX_EFFECTIVE_BALANCE),
                  int(spec.config.EJECTION_BALANCE), _SCAN_CAP)
        reg = _columns(sa, mesh)
        prog = _p_registry_scan(mesh, static)
        _C_PSUMS["registry_updates"].add()
        q_dev, e_dev, el_dev, fam_dev, count = prog(
            reg["aee"], reg["act"], reg["ext"], reg["eff"],
            mesh_state.replicate(_scal([current_epoch, finalized]), mesh))
        fam_counts = np.asarray(fam_dev)
        _C_PARTIALS.add(fam_counts.size)
        if int(fam_counts.max(initial=0)) > _SCAN_CAP:
            # a candidate family outgrew the per-shard index cap — the
            # compact buffers would truncate, so decline and let the
            # columnar engine (full-mask scans, its own exact guards)
            # serve the call: the standard degradation-ladder leg
            _C_SCAN_OVERFLOW.add()
            return False
        active_count = int(np.asarray(count)[0])
        queue_idx = _gather_idx(np.asarray(q_dev), fam_counts[0],
                                _SCAN_CAP)
        eject_idx = _gather_idx(np.asarray(e_dev), fam_counts[1],
                                _SCAN_CAP)
        eligible_idx = _gather_idx(np.asarray(el_dev), fam_counts[2],
                                   _SCAN_CAP)
        if faults.corrupt_armed(SITE):
            # deterministic silent corruption: stamp validator 0 as an
            # activation-queue candidate it is not (or drop it if it
            # is) — exactly the class of wrongness only an audit sees
            if queue_idx.size and int(queue_idx[0]) == 0:
                queue_idx = queue_idx[1:]
            else:
                queue_idx = np.concatenate(
                    [np.zeros(1, dtype=np.int64), queue_idx])
        if supervisor.audit_due(SITE):
            cur = np.uint64(current_epoch)
            g_queue = (cols["aee"] == np.uint64(static[0])) \
                & (cols["eff"] == np.uint64(static[1]))
            g_active = (cols["act"] <= cur) & (cur < cols["ext"])
            g_eject = g_active & (cols["eff"] <= np.uint64(static[2]))
            g_eligible = (cols["aee"] <= np.uint64(finalized)) \
                & (cols["act"] == np.uint64(static[0]))
            ok = bool(
                np.array_equal(queue_idx, np.nonzero(g_queue)[0])
                and np.array_equal(eject_idx, np.nonzero(g_eject)[0])
                and np.array_equal(eligible_idx,
                                   np.nonzero(g_eligible)[0])
                and active_count == int(g_active.sum(dtype=np.int64)))
            supervisor.audit_result(
                SITE, ok, "mesh registry candidate gathers diverged "
                "from the host recomputation")
            if not ok:
                queue_idx = np.nonzero(g_queue)[0]
                eject_idx = np.nonzero(g_eject)[0]
                eligible_idx = np.nonzero(g_eligible)[0]
                active_count = int(g_active.sum(dtype=np.int64))
        # the bounded candidate sets resolve churn-ordered on the host
        # through the SAME body as the single-device engine — cross-
        # shard ordering byte-identical to the spec loop by
        # construction
        ek._registry_apply_idx(spec, state, sa, cols, queue_idx,
                               eject_idx, eligible_idx, active_count)
        return True
    return _dispatch(spec, state, "registry_updates", fast)
