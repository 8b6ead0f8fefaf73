"""Mesh-sharded SPMD engine benchmark (``make bench-mesh-smoke``,
CI-wired).  Runs on whatever devices JAX reports; with
``JAX_PLATFORMS=cpu`` (``make bench-mesh-smoke``) that is an 8-way
virtual host-device mesh (device count forced below, before jax
imports) so the census exercises REAL SPMD partitioning — shard_map
programs, NamedSharding placements, psum collectives — without TPU
hardware.

Four counter-asserted contracts:

1. **psum budget** — a full epoch transition runs every sub-transition
   through the SPMD programs with EXACTLY the budgeted collective count
   per sub-transition (``mesh_epoch.PSUM_BUDGET``); the budget itself
   is proven structurally by a jaxpr census over every reduction and
   elementwise program (a program that silently grew a second
   collective fails here, not in a TPU profile);
2. **byte-identity** — state roots are identical across {mesh on, mesh
   off, spec loops} on the same replay;
3. **per-shard kernel scaling** — on 1M-validator columns, the
   shard-local delta-kernel composition at a full-registry span must
   cost >= 6x its 1/8-registry span (near-linear partition: nothing in
   the per-shard work grows with the GLOBAL registry).  On this 1-core
   host the 8 virtual devices timeshare one core, so wall-clock
   speedup is not measurable — the scaling claim is about the
   per-shard WORK, which is what real 8-device hardware divides;
4. **leaf-span merkleization** — the mesh level build of a 64k-chunk
   buffer is byte-identical to the sequential build, levels included.

Exits nonzero on any violation.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the mesh needs addressable devices BEFORE the first jax import; on a
# TPU host the real topology wins, on the CPU platform this is the
# 8-way virtual mesh the CI legs use
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_force_host_platform_device_count=8").strip()

from bench_state_arrays import build_state  # noqa: E402


def _psum_census(mesh):
    """Structural proof of the collective budget: count psum equations
    in every compiled program family's jaxpr."""
    import jax
    import numpy as np
    from consensus_specs_tpu.parallel import mesh_epoch, mesh_state

    n_dev = mesh.shape[mesh_state.AXIS]
    n = 4 * n_dev
    u64 = lambda: np.zeros(n, dtype=np.uint64)       # noqa: E731
    u8 = lambda: np.zeros(n, dtype=np.uint8)         # noqa: E731
    bl = lambda: np.zeros(n, dtype=bool)             # noqa: E731
    scal8 = np.zeros(8, dtype=np.uint64)

    def count(prog, *args):
        with mesh_state.x64():
            return str(jax.make_jaxpr(prog)(*args)).count("psum")

    census = {
        "altair_sums": count(
            mesh_epoch._p_altair_sums(mesh, 3),
            u64(), u64(), u64(), bl(), u8(), scal8),
        "masked_sums": count(
            mesh_epoch._p_masked_sums(mesh),
            u64(), np.zeros((4, n), dtype=bool)),
        "active_sums": count(
            mesh_epoch._p_active_sums(mesh, 0),
            u64(), u64(), u64(), scal8),
        "shard_stats": count(
            mesh_epoch._p_shard_stats(mesh, 3),
            u64(), u64(), u64()),
        "registry_scan": count(
            mesh_epoch._p_registry_scan(mesh, (2**64 - 1, 32, 16, 256)),
            u64(), u64(), u64(), u64(), scal8),
        "altair_deltas": count(
            mesh_epoch._p_altair_deltas(
                mesh, (False, (14, 26, 14), 64, 10**9, 2, 1)),
            u64(), u64(), u64(), bl(), u64(), u8(), u64(), u64(), scal8),
        "inactivity": count(
            mesh_epoch._p_inactivity(mesh, (4, 16, False, 1)),
            u64(), u64(), bl(), u64(), u8(), u64(), scal8),
        "slashings": count(
            mesh_epoch._p_slashings(mesh, (10**9,)),
            u64(), bl(), u64(), u64(), scal8),
        "eff_balance": count(
            mesh_epoch._p_eff_balance(
                mesh, (10**9, 10**8, 10**8, 32 * 10**9)),
            u64(), u64()),
    }
    assert census["altair_sums"] == 1, census
    assert census["masked_sums"] == 1, census
    assert census["active_sums"] == 1, census
    assert census["registry_scan"] == 1, census
    for name in ("altair_deltas", "inactivity", "slashings",
                 "eff_balance", "shard_stats"):
        assert census[name] == 0, \
            f"elementwise program {name} grew a collective: {census}"
    return census


def _shard_kernel_time(n, iters=3):
    """Wall time of the shard-local altair delta composition (the same
    shared kernels the SPMD program maps) over an ``n``-lane span."""
    import numpy as np
    from consensus_specs_tpu.ops import epoch_kernels as ek

    rng = np.random.default_rng(11)
    eff = rng.integers(1, 33, n, dtype=np.uint64) * np.uint64(10**9)
    balances = eff.copy()
    scores = rng.integers(0, 50, n, dtype=np.uint64)
    eligible = rng.random(n) < 0.95
    parts = [rng.random(n) < 0.7 for _ in range(3)]
    base_reward = (eff // np.uint64(10**9)) * np.uint64(512)
    best = None
    for _ in range(iters):
        t0 = time.perf_counter()
        acc = balances
        for f, w in enumerate((14, 26, 14)):
            r, p = ek.flag_deltas_kernel(
                np, base_reward, eligible, parts[f], weight=w,
                weight_denominator=64, participating_increments=900,
                active_increments=1000, in_leak=False,
                is_head_flag=f == 2)
            acc = ek.apply_deltas_kernel(np, acc, r, p)
        inact = ek.inactivity_penalty_kernel(
            np, eff, scores, eligible, parts[1],
            denominator=4 * 3 * 10**7)
        acc = ek.apply_deltas_kernel(
            np, acc, np.zeros(n, dtype=np.uint64), inact)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--validators", type=int, default=2048,
                    help="differential-leg registry size")
    ap.add_argument("--census-validators", type=int, default=1 << 20,
                    help="scaling-census column length (1M default)")
    ap.add_argument("--merkle-chunks", type=int, default=1 << 16)
    ap.add_argument("--min-scaling", type=float, default=6.0)
    args = ap.parse_args()

    from consensus_specs_tpu.utils.jax_env import setup_compile_cache
    setup_compile_cache()

    import numpy as np
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.ops import epoch_kernels as ek
    from consensus_specs_tpu.parallel import mesh_epoch, mesh_merkle, \
        mesh_state
    from consensus_specs_tpu.state import arrays
    from consensus_specs_tpu.test_infra.metrics import counting
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.ssz import hash_tree_root

    bls.bls_active = False
    assert mesh_state.device_count() >= 2, \
        "mesh bench needs a multi-device host (virtual mesh forced " \
        "above — did an ambient XLA_FLAGS override it?)"
    mesh = mesh_state.build_mesh()
    n_dev = mesh_state.device_count()

    # -- 1: structural psum census -----------------------------------------
    census = _psum_census(mesh)

    # -- 2: differential replay, mesh counters ----------------------------
    spec = build_spec("altair", "minimal")
    slots_per_epoch = int(spec.SLOTS_PER_EPOCH)
    state = build_state(spec, args.validators)
    ek.use_vectorized()
    arrays.use_arrays()
    mesh_state.use_fallback()
    spec.process_slots(state, slots_per_epoch)      # genesis no-op epoch
    for i in range(args.validators):
        state.previous_epoch_participation[i] = \
            spec.ParticipationFlags(i % 8)
        state.inactivity_scores[i] = i % 40

    s_loop, s_single, s_mesh = state.copy(), state.copy(), state.copy()
    ek.use_loops()
    spec.process_slots(s_loop, int(s_loop.slot) + slots_per_epoch)
    root_loop = bytes(hash_tree_root(s_loop))
    ek.use_vectorized()
    spec.process_slots(s_single, int(s_single.slot) + slots_per_epoch)
    root_single = bytes(hash_tree_root(s_single))
    mesh_state.use_mesh()
    t0 = time.time()
    with counting() as delta:
        spec.process_slots(s_mesh, int(s_mesh.slot) + slots_per_epoch)
        root_mesh = bytes(hash_tree_root(s_mesh))
    mesh_replay_s = time.time() - t0
    mesh_state.use_auto()

    mesh_subs = delta["mesh.epoch{path=mesh}"]
    psums = {sub: delta[f"mesh.psums{{site={sub}}}"]
             for sub in mesh_epoch.PSUM_BUDGET}
    host_partials = delta["mesh.host_partials"]

    # -- 3: per-shard kernel scaling census at 1M --------------------------
    n_full = args.census_validators
    t_full = _shard_kernel_time(n_full)
    t_shard = _shard_kernel_time(n_full // n_dev)
    scaling = t_full / t_shard if t_shard else float("inf")

    # one real 1M SPMD dispatch for the record (8 shards timeshare this
    # host's core — wall time here is compile+dispatch overhead, the
    # scaling claim above is the hardware-relevant number)
    rng = np.random.default_rng(5)
    cols_1m = rng.integers(0, 2**35, n_full, dtype=np.uint64)
    with mesh_state.x64():
        t0 = time.time()
        dev = mesh_state.place(cols_1m, mesh)
        sums = np.asarray(mesh_epoch._p_masked_sums(mesh)(
            dev, np.ones((1, n_full), dtype=bool)))
        place_reduce_s = time.time() - t0
    assert int(sums[0]) == int(cols_1m.sum(dtype=np.uint64)), \
        "1M psum reduction diverged from the host sum"

    # -- 4: leaf-span merkleization ----------------------------------------
    data = rng.integers(0, 256, args.merkle_chunks * 32,
                        dtype=np.uint8).tobytes()
    mesh_state.use_mesh()
    with counting() as mdelta:
        t0 = time.time()
        levels = mesh_merkle.build_levels(data, 40)
        mesh_merkle_s = time.time() - t0
    mesh_state.use_fallback()
    t0 = time.time()
    golden = mesh_merkle._sequential_levels(data, 40)
    seq_merkle_s = time.time() - t0
    mesh_state.use_auto()
    assert levels is not None, "mesh merkle declined the 64k build"
    assert all(bytes(a) == bytes(b) for a, b in zip(levels, golden)), \
        "mesh leaf-span levels diverged from the sequential build"

    result = {
        "metric": "mesh SPMD engine",
        "devices": n_dev,
        "validators": args.validators,
        "census_validators": n_full,
        "psum_census": census,
        "epoch_psums": psums,
        "host_partial_elements": host_partials,
        "mesh_subtransitions": mesh_subs,
        "mesh_replay_s": round(mesh_replay_s, 3),
        "shard_kernel_full_s": round(t_full, 4),
        "shard_kernel_eighth_s": round(t_shard, 4),
        "per_shard_scaling": round(scaling, 2),
        "place_reduce_1m_s": round(place_reduce_s, 3),
        "mesh_merkle_chunks": args.merkle_chunks,
        "mesh_merkle_s": round(mesh_merkle_s, 3),
        "seq_merkle_s": round(seq_merkle_s, 3),
        "mesh_merkle_builds": mdelta["mesh.merkle{path=mesh}"],
    }
    print(json.dumps(result), flush=True)

    # the census guarantees (the smoke's reason to exist)
    assert root_mesh == root_single == root_loop, \
        "state roots diverge across {mesh, single-device, spec loop}"
    assert mesh_subs == 5, \
        f"expected all 5 altair sub-transitions through the mesh: " \
        f"{mesh_subs}"
    assert psums == mesh_epoch.PSUM_BUDGET, \
        f"psum count off budget: {psums} != {mesh_epoch.PSUM_BUDGET}"
    assert delta["mesh.epoch.fallbacks{reason=guard}"] == 0, \
        "unexpected mesh guard fallback"
    # host-work census: the runtime twin of the speclint N13xx proof —
    # across the whole mesh epoch the host read only per-shard partial
    # stacks (10S elements for the altair composition: 3S rewards
    # maxima + S inactivity + 3S registry candidate counts + S
    # slashings + 2S effective-balance), never an O(n) column
    assert 0 < host_partials <= 16 * n_dev, \
        f"host partial reads off budget: {host_partials} elements " \
        f"for {n_dev} shards (expected ~10S, hard cap 16S)"
    assert scaling >= args.min_scaling, \
        f"per-shard kernel scaling {scaling:.2f}x < " \
        f"{args.min_scaling}x at {n_dev} shards"
    assert mdelta["mesh.merkle{path=mesh}"] == 1


if __name__ == "__main__":
    main()
