"""Compile-only checks of the main-path device programs for a TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2): these
tests lower and compile a few of the staged BLS programs that one
block's RLC flush dispatches, at Leg A widths of ``chip_smoke.py`` (256
lanes: 131 items pad to the 256 bucket; 512-key pubkey rows), so a PR
that breaks the chip's compile or blows its memory fails here at no
chip time.  Nothing runs; no time is measured.

The topology is described inside a module fixture (never at import,
in ``skipif`` or ``parametrize``: only one process may load libtpu, and
every xdist worker imports this file).  The persistent compile cache is
off around these compiles: an entry compiled for a described chip
cannot be read back without one.
"""
import pytest

import jax
import jax.numpy as jnp

LANES = 256          # lane_bucket(128 aggregates + sync + 2 singles)
PUBKEYS = 512        # per-aggregate pubkey row, padded
NLIMB = 24
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shape tree -> the same shapes placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes > one v5e's HBM"
    return used


def _g1(lanes):
    return (_u32(lanes, NLIMB),) * 3


def _g2_affine(lanes):
    return ((_u32(lanes, NLIMB),) * 2, (_u32(lanes, NLIMB),) * 2)


def test_pubkey_aggregation(one_chip):
    """The per-item pubkey tree sum over (256, 512) rows: the largest
    device buffer of the flush.  The halving tree holds ~3.8 GB in all;
    a form that adds the full grid at every level (~7.5 GB) fails the
    band."""
    from consensus_specs_tpu.ops import bls_jax
    pk = tuple(_u32(LANES, PUBKEYS, NLIMB) for _ in range(3))
    used = _compile(bls_jax._j_tree_sum, _on(one_chip, pk))
    assert 2**30 < used < 5 * 2**30, used


def test_rlc_scalar_multiplication(one_chip):
    """The 128-bit per-item RLC scale of the aggregated pubkeys."""
    from consensus_specs_tpu.ops import bls_jax
    args = (_g1(LANES), _u32(LANES, bls_jax.RLC_SCALAR_BITS))
    _compile(bls_jax._j_g1_scale, *_on(one_chip, args))


def test_inversion_ladder(one_chip):
    """The shared fixed-exponent ladder every staged inversion, square
    root and Legendre symbol dispatches through."""
    from consensus_specs_tpu.ops.jax_bls import limbs as L
    args = (_u32(LANES, NLIMB), _u32(len(L.INV_WINDOWS)))
    _compile(L._j_pow_windows, *_on(one_chip, args))


def test_g1_normalize(one_chip):
    from consensus_specs_tpu.ops.jax_bls import points as PT
    args = (_g1(LANES), _u32(LANES, NLIMB))
    _compile(PT._j_g1_norm_post, *_on(one_chip, args))


def test_hash_to_curve_sswu_front(one_chip):
    """The SSWU front stage of hash-to-G2 (u0/u1 stacked: 2 x 256)."""
    from consensus_specs_tpu.ops.jax_bls import htc as HTC
    u = (_u32(2, LANES, NLIMB), _u32(2, LANES, NLIMB))
    _compile(HTC._j_sswu_tv, _on(one_chip, u))


def test_miller_loop_ends(one_chip):
    """The product pairing's Miller-loop entry and exit over the flat
    256-pair axis."""
    from consensus_specs_tpu.ops.jax_bls import pairing as PR
    q = _g2_affine(LANES)
    _compile(PR._j_miller_init, _on(one_chip, q))
    carry = jax.eval_shape(PR._j_miller_init, q)
    degenerate = jax.ShapeDtypeStruct((LANES,), jnp.bool_)
    _compile(PR._j_miller_finish, *_on(one_chip, (carry, degenerate)))
