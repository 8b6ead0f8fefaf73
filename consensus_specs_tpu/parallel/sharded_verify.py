"""Sharded batched FastAggregateVerify over a 2D device mesh.

Mesh axes: ``data`` (independent aggregate verifications — a block's
attestations) x ``agg`` (the pubkey-aggregation tree of each
verification).  Each shard tree-sums its local pubkey slice; partials
``all_gather`` across the ``agg`` axis and combine on-device (complete
point addition is not a ``psum``-able monoid over raw limb vectors, so
the collective carries partial sums); the hash-to-curve and pairing
stages then run data-parallel.  Scales to multi-host the way the
reference's Rust FFI loop cannot: the same program spans ICI within a
slice and DCN across slices purely through the mesh.

Structure: ONLY the collective aggregation is a ``shard_map`` program;
everything downstream reuses the bounded staged programs from
``ops.bls_jax`` / ``ops.jax_bls.pairing`` — GSPMD propagates the data
sharding through them.  (A monolithic sharded module is exactly the
shape XLA:CPU's fusion pass cannot compile on the 1-core dryrun host —
the round-1/round-2 dryrun timeouts.)

``__graft_entry__.dryrun_multichip`` and ``tests/test_multichip.py``
exercise this on the 8-device virtual CPU mesh (or real chips).
"""
import numpy as np
import jax


def _gather_and_combine(part, axis_name: str, n_shards: int, add=None):
    """all_gather per-shard partial point sums along ``axis_name`` and
    combine them in a fixed order on every device (complete point
    addition is not a ``psum``-able monoid over raw limb vectors, so
    the collective must carry partial sums).  ``part`` leaves must have
    the shard axis at position 0 after the gather.  ``add`` selects the
    group law (default G1 complete addition; pass ``PT.g2_add`` for the
    G2 collectives)."""
    from consensus_specs_tpu.ops.jax_bls import points as PT
    if add is None:
        add = PT.g1_add
    gathered = jax.tree_util.tree_map(
        lambda a: jax.lax.all_gather(a, axis_name), part)
    total = jax.tree_util.tree_map(lambda a: a[0], gathered)
    for i in range(1, n_shards):  # noqa: J203 (static unroll: mesh size)
        total = add(
            total, jax.tree_util.tree_map(lambda a, i=i: a[i], gathered))
    return total


def build_mesh(devices, data: int, agg: int):
    """(data x agg) Mesh over the given devices."""
    from jax.sharding import Mesh
    dev = np.array(list(devices)[:data * agg]).reshape(data, agg)
    return Mesh(dev, ("data", "agg"))


def make_sharded_agg(mesh):
    """Compile the COLLECTIVE half for ``mesh``: per-shard partial G1
    tree sums over the local pubkey slice, ``all_gather`` across 'agg',
    ordered combine on every device.  Returns ``agg(pk_pts) ->
    total[data_batch]`` (unnormalized projective aggregate).

    Exposed separately from the full step so tests can check the
    collective alone.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from consensus_specs_tpu.ops.jax_bls import points as PT

    agg_size = mesh.shape["agg"]

    def local_agg(pk_pts):
        # per-shard partial aggregation over the local pubkey slice,
        # then the shared gather + ordered combine
        part = PT.g1_tree_sum_batched(pk_pts)
        return _gather_and_combine(part, "agg", agg_size)

    pk_spec = P("data", "agg")
    return jax.jit(shard_map(
        local_agg, mesh=mesh, in_specs=((pk_spec,) * 3,),
        out_specs=P("data"), check_vma=False))


def make_sharded_agg_verify(mesh):
    """Compile a sharded verification step for ``mesh``.

    Returns ``step(pk_pts, u0, u1, sig_q, agg_degen, sig_degen) ->
    bool[data_batch]`` where ``pk_pts`` is a packed projective G1 pytree
    of shape ``(batch, n_keys)`` sharded ``P('data', 'agg')`` and the
    rest are data-sharded (see ``bls_jax.verify_aggregates_batch`` for
    the packing).  Downstream of the collective this IS
    ``bls_jax.verify_from_aggregate`` - one shared implementation.
    """
    from consensus_specs_tpu.ops import bls_jax

    sharded_agg = make_sharded_agg(mesh)

    def step(pk_pts, u0, u1, sig_q, agg_degen, sig_degen):
        return bls_jax.verify_from_aggregate(
            sharded_agg(pk_pts), u0, u1, sig_q, agg_degen, sig_degen)

    return step


def make_sharded_msm(mesh_devices):
    """Compile a POINTS-sharded multi-scalar multiplication.

    The ``g1_lincomb`` hot path at pod scale (SURVEY §2.4: "shard MSM
    over devices with shard_map, reduce over ICI"): the point/scalar
    axis is split across a 1D ``points`` mesh, each device runs the
    digit-parallel windowed MSM core over its slice, and the per-shard
    partial sums ``all_gather`` and combine on-device — the same
    collective pattern as the aggregation tree (point addition is not a
    ``psum``-able monoid over raw limb vectors).

    Returns ``msm(window_pts, digit_bits) -> packed G1 total`` where
    the inputs are the window expansion / bit planes produced by
    ``ops.jax_bls.msm`` (``_flatten_windows``/``_digits_msb_bits``),
    both shaped ``(N_WINDOWS * n_points, ...)`` and sharded along that
    leading axis.  n_points must divide evenly by the mesh size.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from consensus_specs_tpu.ops.jax_bls import points as PT
    from consensus_specs_tpu.ops.jax_bls import msm as M
    from consensus_specs_tpu.parallel import mesh_state

    mesh_devices = tuple(mesh_devices)
    mesh = mesh_state.build_mesh("points", mesh_devices)
    n_shards = mesh.shape["points"]

    def local_msm(window_pts, digit_bits):
        part = M._msm_core(window_pts, digit_bits)     # local partial
        # all_gather inserts the shard axis at 0 and g1_add is
        # elementwise over limb leaves, so rank-1 parts pass straight in
        return _gather_and_combine(part, "points", n_shards)

    spec = P("points")
    return jax.jit(shard_map(
        local_msm, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: spec, (0, 0, 0)),
                  spec),
        out_specs=P(), check_vma=False))


def make_sharded_g2_msm(mesh_devices):
    """Compile a POINTS-sharded G2 multi-scalar multiplication.

    The RLC batch verifier's signature fold ``sum_i [r_i] sig_i``
    (``ops/bls_jax.rlc_combined_check``) at pod scale: the signature
    axis splits across a 1D ``points`` mesh, each device runs the
    per-lane double-and-add + local tree sum over its slice, and the
    per-shard partial G2 sums ``all_gather`` and combine on-device —
    the same collective pattern as the G1 aggregation tree.

    Returns ``msm(sig_pts, bits) -> packed G2 total`` where ``sig_pts``
    is a packed projective G2 pytree of shape ``(B, ...)`` and ``bits``
    the ``(B, n_bits)`` MSB-first scalar bit planes
    (``ops.bls_jax._bits_msb``), both sharded along the leading axis.
    B must divide evenly by the mesh size.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from consensus_specs_tpu.ops.jax_bls import points as PT
    from consensus_specs_tpu.parallel import mesh_state

    mesh_devices = tuple(mesh_devices)
    mesh = mesh_state.build_mesh("points", mesh_devices)
    n_shards = mesh.shape["points"]

    def local_msm(sig_pts, bits):
        part = PT.g2_tree_sum(PT.g2_scalar_mul(sig_pts, bits))
        return _gather_and_combine(part, "points", n_shards, add=PT.g2_add)

    spec = P("points")
    g2_tree_spec = jax.tree_util.tree_map(
        lambda _: spec, ((0, 0), (0, 0), (0, 0)))
    return jax.jit(shard_map(
        local_msm, mesh=mesh, in_specs=(g2_tree_spec, spec),
        out_specs=P(), check_vma=False))


_SHARDED_G2_MSM_CACHE = {}


def sharded_g2_msm_for(devices: tuple = None):
    """Memoized compiled G2-MSM program per device tuple (same rationale
    as :func:`_sharded_msm_for`: rebuilding the ``shard_map`` closure
    would defeat jit's identity-keyed cache).  ``devices`` defaults to
    the whole host mesh — the shape is derived from ``jax.devices()``,
    never hardcoded."""
    if devices is None:
        devices = jax.devices()
    devices = tuple(devices)
    prog = _SHARDED_G2_MSM_CACHE.get(devices)
    if prog is None:
        prog = make_sharded_g2_msm(devices)
        _SHARDED_G2_MSM_CACHE[devices] = prog
    return prog


def sharded_g2_msm_padded(sig_packed, bits, devices: tuple = None):
    """Host API for the RLC signature fold at ANY batch size: pads the
    signature axis up to a multiple of the mesh with identity lanes
    (infinity points, zero scalar bits — the same padding the
    single-device fold already uses for its lane bucket) and runs the
    points-sharded program.  Scales the MULTICHIP_r05 8-device dryrun
    shape to whatever ``jax.devices()`` answers, uneven shards
    included."""
    from consensus_specs_tpu.ops.jax_bls import points as PT
    from consensus_specs_tpu.ops.bls12_381.curve import G2Point
    if devices is None:
        devices = jax.devices()
    devices = tuple(devices)
    b = jax.tree_util.tree_leaves(sig_packed)[0].shape[0]
    pad = (-b) % len(devices)
    if pad:
        inf = PT.g2_pack([G2Point.inf()] * pad)
        sig_packed = jax.tree_util.tree_map(
            lambda a, i: np.concatenate(
                [np.asarray(a), np.asarray(i)], axis=0), sig_packed, inf)
        bits = np.asarray(bits)
        bits = np.concatenate(
            [bits, np.zeros((pad,) + bits.shape[1:], dtype=bits.dtype)])
    return sharded_g2_msm_for(devices)(sig_packed, bits)


_SHARDED_MSM_CACHE = {}


def _sharded_msm_for(devices: tuple):
    """Memoized compiled program per device tuple: rebuilding the
    ``shard_map`` closure on every call would defeat jit's identity-
    keyed cache (~90 s compile per call on a 1-core host)."""
    prog = _SHARDED_MSM_CACHE.get(devices)
    if prog is None:
        prog = make_sharded_msm(devices)
        _SHARDED_MSM_CACHE[devices] = prog
    return prog


_SHARDED_WINDOW_CACHE = {}


def sharded_g1_msm(points, scalars, devices, cache_key=None):
    """Host API: MSM over oracle ``G1Point``s sharded across ``devices``.

    Pads the point list to a multiple of the device count with infinity
    points (zero scalars), so any size works.  ``cache_key``: hashable
    id for a FIXED basis (the KZG trusted setup) so the 248-doubling
    per-shard window expansions run once per process, mirroring
    ``ops.jax_bls.msm.g1_msm``'s setup cache.
    """
    from consensus_specs_tpu.ops.jax_bls import points as PT
    from consensus_specs_tpu.ops.jax_bls import msm as M
    from consensus_specs_tpu.ops.bls12_381.curve import G1Point

    assert len(points) == len(scalars)
    if not points:
        return G1Point.inf()
    from consensus_specs_tpu.ops.bls12_381.fields import R_ORDER
    devices = tuple(devices)
    n_dev = len(devices)
    pts = list(points)
    # canonical reduction before digit extraction (matches g1_lincomb):
    # _digits_msb_bits reads 256 two's-complement bits, so a negative or
    # >= 2**256 scalar would otherwise yield a silently wrong MSM
    sc = [int(s) % R_ORDER for s in scalars]
    pad = (-len(pts)) % n_dev
    pts += [G1Point.inf()] * pad
    sc += [0] * pad
    # window-major flattening interleaves windows of ALL points; shard
    # by point instead: expand per shard
    per = len(pts) // n_dev
    msm = _sharded_msm_for(devices)
    full_key = (cache_key, devices, len(pts)) if cache_key is not None \
        else None
    window_pts = _SHARDED_WINDOW_CACHE.get(full_key) \
        if full_key is not None else None
    if window_pts is None:
        wins = []
        for s in range(n_dev):
            packed = PT.g1_pack(pts[s * per:(s + 1) * per])
            wins.append(M._flatten_windows(M._expand_windows(packed)))
        window_pts = jax.tree_util.tree_map(
            lambda *a: np.concatenate(a, axis=0), *wins)
        if full_key is not None:
            _SHARDED_WINDOW_CACHE[full_key] = window_pts
    digit_bits = np.concatenate(
        [M._digits_msb_bits(sc[s * per:(s + 1) * per])
         for s in range(n_dev)], axis=0)
    out = msm(window_pts, digit_bits)
    return PT.g1_unpack(out)
