"""Multi-device sharding tests (8-device virtual CPU mesh from conftest).

Covers the distributed axis of the framework: the pubkey-aggregation
tree split over a device mesh with an ``all_gather`` combine — the TPU
analog of the reference's per-attestation serial FFI loop
(``specs/phase0/beacon-chain.md:1757-1774``) — plus the driver-facing
``__graft_entry__.dryrun_multichip`` path itself.
"""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from consensus_specs_tpu.utils.env_flags import HEAVY  # noqa: E402


def _require_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices, have {len(jax.devices())}")


@pytest.mark.skipif(not HEAVY, reason="shard_map compile is ~90 s on a "
                    "1-core host; the collective is default-covered by "
                    "test_sharded_sum_collective_layout and the driver "
                    "dryrun (CS_TPU_HEAVY=1)")
def test_sharded_g1_aggregate_matches_host():
    """Partial G1 sums per shard + all_gather combine == host aggregation."""
    _require_devices(8)
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.ops.jax_bls import points as PT
    from consensus_specs_tpu.ops import bls_jax

    n_shards = 8
    keys_per_shard = 2
    n_keys = n_shards * keys_per_shard
    bls.use_py()
    pks = [bls_jax._decompress_g1(bls.SkToPk(sk)) for sk in range(1, n_keys + 1)]
    expected = bls.AggregatePKs([bls.SkToPk(sk) for sk in range(1, n_keys + 1)])

    packed = PT.g1_pack(pks)
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("agg",))

    def local(pk_pts):
        part = PT.g1_tree_sum(pk_pts)
        gathered = jax.tree_util.tree_map(
            lambda a: jax.lax.all_gather(a, "agg"), part)
        total = jax.tree_util.tree_map(lambda a: a[0], gathered)
        for i in range(1, n_shards):  # noqa: J203 (static unroll: mesh size)
            total = PT.g1_add(
                total, jax.tree_util.tree_map(lambda a: a[i], gathered))
        return PT.g1_normalize(total)

    step = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("agg"), packed),),
        out_specs=P(), check_vma=False))
    out = step(packed)
    got = PT.g1_unpack(jax.tree_util.tree_map(lambda a: a[None], out))
    assert got.to_compressed() == expected


@pytest.mark.skipif(not HEAVY, reason="full pairing execution (CS_TPU_HEAVY=1)")
def test_sharded_verify_module_end_to_end():
    """consensus_specs_tpu.parallel: the library sharded-verify step
    accepts valid aggregates and rejects a wrong message."""
    _require_devices(8)
    import __graft_entry__ as ge
    from consensus_specs_tpu.parallel import build_mesh, \
        make_sharded_agg_verify

    mesh = build_mesh(jax.devices()[:8], 2, 4)
    pk_pts, u0, u1, sig_q, agg_degen, sig_degen = ge._example_inputs(
        batch=4, n_keys=8)
    step = make_sharded_agg_verify(mesh)
    out = np.asarray(step(pk_pts, u0, u1, sig_q, agg_degen, sig_degen))
    assert out.shape == (4,) and bool(out.all())
    # wrong message: duplicate u0 — H = map(u0) + map(u0) != map(u0)+map(u1)
    # (swapping u0/u1 would be a no-op: the two mapped points are summed)
    out_bad = np.asarray(step(pk_pts, u0, u0, sig_q, agg_degen, sig_degen))
    assert not bool(out_bad.any())


def test_sharded_sum_collective_layout():
    """Sanity: the mesh really has 8 addressable devices and psum runs."""
    _require_devices(8)
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
    x = jnp.arange(8.0)
    f = jax.jit(shard_map(
        lambda v: jax.lax.psum(v, "d"), mesh=mesh,
        in_specs=P("d"), out_specs=P()))
    assert float(f(x)[0]) == 28.0


@pytest.mark.skipif(not HEAVY, reason="full pairing dryrun (CS_TPU_HEAVY=1)")
def test_dryrun_multichip_full():
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


@pytest.mark.skipif(not HEAVY, reason="MSM shard_map compile on a 1-core "
                    "host (CS_TPU_HEAVY=1)")
def test_sharded_msm_matches_host():
    """Points-sharded MSM over the 8-device mesh equals the host
    Pippenger result (SURVEY 2.4: shard MSM over devices, reduce over
    the mesh collective)."""
    _require_devices(4)
    from consensus_specs_tpu.parallel.sharded_verify import sharded_g1_msm
    from consensus_specs_tpu.ops.bls12_381.curve import G1_GENERATOR, G1Point

    from consensus_specs_tpu.ops.bls12_381.fields import R_ORDER

    pts = [G1_GENERATOR.mult(k) for k in (1, 3, 7, 11, 13, 17, 19, 23)]
    # non-canonical scalars ride along: a negative and a >= 2**256 value
    # must be reduced mod the group order before digit extraction
    # (regression: unreduced two's-complement bits gave a wrong MSM)
    scalars = [5, -9, 2**256 + 2, 31, R_ORDER + 1, 8, 27, 4]
    expect = G1Point.inf()
    for p, s in zip(pts, scalars):
        expect = expect + p.mult(s % R_ORDER)
    got = sharded_g1_msm(pts, scalars, jax.devices()[:4])
    assert got == expect

    # ragged size: padding with infinity points must not change the sum
    got2 = sharded_g1_msm(pts[:5], scalars[:5], jax.devices()[:4])
    expect2 = G1Point.inf()
    for p, s in zip(pts[:5], scalars[:5]):
        expect2 = expect2 + p.mult(s)
    assert got2 == expect2


@pytest.mark.skipif(not HEAVY, reason="G2 MSM shard_map compile on a "
                    "1-core host (CS_TPU_HEAVY=1)")
def test_sharded_g2_msm_matches_host():
    """Points-sharded G2 MSM (the RLC signature fold
    ``sum_i [r_i] sig_i``) over the virtual mesh equals the oracle
    Pippenger result."""
    _require_devices(4)
    from consensus_specs_tpu.parallel.sharded_verify import sharded_g2_msm_for
    from consensus_specs_tpu.ops import bls_jax
    from consensus_specs_tpu.ops.jax_bls import points as PT
    from consensus_specs_tpu.ops.bls12_381.curve import (
        g2_from_compressed, msm as oracle_msm)
    from consensus_specs_tpu.utils import bls

    bls.use_py()
    sigs = [g2_from_compressed(bls.Sign(i, bytes([i]) * 32))
            for i in range(1, 9)]
    rng = np.random.RandomState(42)
    rs = [int.from_bytes(rng.bytes(16), "little") | 1 for _ in sigs]
    prog = sharded_g2_msm_for(tuple(jax.devices()[:4]))
    out = prog(PT.g2_pack(sigs),
               jnp.asarray(bls_jax._bits_msb(rs, bls_jax.RLC_SCALAR_BITS)))
    got = PT.g2_unpack(jax.tree_util.tree_map(lambda a: a[None], out))
    assert got == oracle_msm(sigs, rs)
