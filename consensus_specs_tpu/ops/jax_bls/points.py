"""G1/G2 point arithmetic for TPU: complete projective formulas, branchless.

Points are pytrees ``(X, Y, Z)`` of field elements in homogeneous projective
coordinates - G1 over Fq (limb arrays), G2 over Fq2 (limb pairs).  The
addition law is the Renes-Costello-Batina complete formula for short
Weierstrass curves with a = 0, which is total: it handles doubling,
inverses and the identity (0 : 1 : 0) with no branches, exactly what an XLA
program wants (reference's backends use branchy Jacobian code in Rust;
branchless completeness is the TPU-first redesign).

Scalar multiplication and multi-point aggregation are ``lax.scan`` /
tree-reduction over these complete adds, so aggregating 2048 attestation
pubkeys is a depth-11 vectorized reduction rather than a serial loop
(reference hot path ``eth2spec/utils/bls.py:133-143``).
"""
import jax  # tree_util only; array ops ride the backend switch
from .backend import xp as jnp, lax, kjit

from consensus_specs_tpu.ops.bls12_381.fields import Fq2 as _OFq2
from consensus_specs_tpu.ops.bls12_381.curve import G1Point, G2Point
from . import limbs as L
from . import tower as T


# Field-op dispatch: G1 coords are Fq limb arrays, G2 coords are Fq2 pairs.
class _FqOps:
    add = staticmethod(L.add_mod)
    sub = staticmethod(L.sub_mod)
    neg = staticmethod(L.neg_mod)
    mul = staticmethod(L.mont_mul)
    sqr = staticmethod(L.mont_sqr)
    mul_many = staticmethod(L.mont_mul_many)
    add_many = staticmethod(L.add_mod_many)
    sub_many = staticmethod(L.sub_mod_many)
    select = staticmethod(L.select)
    is_zero = staticmethod(L.is_zero)
    eq = staticmethod(L.eq)

    @staticmethod
    def zero_like(x):
        return jnp.zeros_like(x)

    @staticmethod
    def one_like(x):
        return jnp.broadcast_to(jnp.asarray(L.ONE_M), x.shape)


class _Fq2Ops:
    add = staticmethod(T.f2_add)
    sub = staticmethod(T.f2_sub)
    neg = staticmethod(T.f2_neg)
    mul = staticmethod(T.f2_mul)
    sqr = staticmethod(T.f2_sqr)
    mul_many = staticmethod(T.f2_mul_many)
    add_many = staticmethod(T.f2_add_many)
    sub_many = staticmethod(T.f2_sub_many)
    select = staticmethod(T.f2_select)
    is_zero = staticmethod(T.f2_is_zero)
    eq = staticmethod(T.f2_eq)
    zero_like = staticmethod(T.f2_zero_like)
    one_like = staticmethod(T.f2_one_like)


# 3*b curve constants: b = 4 on G1, b = 4(1+u) on G2.
_B3_G1 = L.fq_const(12)
_B3_G2 = _OFq2(12, 12)


def _b3(f, like):
    if f is _FqOps:
        return jnp.broadcast_to(jnp.asarray(_B3_G1), like.shape)
    return T.f2_broadcast(T.f2_const(_B3_G2), like)


def _complete_add(f, p, q):
    """RCB 2015 Algorithm 7 (complete addition, a = 0, projective).

    Multiplications in three batched waves (6 + 2 + 6), and every group
    of independent adds/subs in one batched wave too — XLA:CPU compile
    cost is ~linear in the number of carry networks, so singles are the
    enemy.
    """
    x1, y1, z1 = p
    x2, y2, z2 = q
    b3 = _b3(f, x1)
    s = f.add_many([(x1, y1), (y1, z1), (x1, z1),
                    (x2, y2), (y2, z2), (x2, z2)])
    t0, t1, t2, m1, m2, m3 = f.mul_many([
        (x1, x2), (y1, y2), (z1, z2),
        (s[0], s[3]), (s[1], s[4]), (s[2], s[5])])
    w = f.add_many([(t0, t1), (t1, t2), (t0, t2), (t0, t0)])
    t3, t4, yp = f.sub_many([(m1, w[0]), (m2, w[1]), (m3, w[2])])
    x3 = f.add_many([(w[3], t0)])[0]                   # 3 x1x2
    t2b, y3 = f.mul_many([(b3, t2), (b3, yp)])
    z3 = f.add_many([(t1, t2b)])[0]                    # y1y2 + 3b z1z2
    t1b = f.sub_many([(t1, t2b)])[0]                   # y1y2 - 3b z1z2
    p1, p2, p3, p4, p5, p6 = f.mul_many([
        (t3, t1b), (t4, y3), (t1b, z3), (y3, x3), (z3, t4), (x3, t3)])
    fin_a = f.add_many([(p3, p4), (p5, p6)])
    return (f.sub_many([(p1, p2)])[0], fin_a[0], fin_a[1])


def _complete_dbl(f, p):
    """RCB 2015 Algorithm 9 (exception-free doubling, a = 0, projective):
    9 muls in three batched waves vs 12 for the general complete add;
    adds wave-batched like :func:`_complete_add`.
    The identity (and any y = 0 input) correctly lands on (0 : c : 0)."""
    X, Y, Z = p
    b3 = _b3(f, X)
    t0, t1, xy, zz = f.mul_many([(Y, Y), (Y, Z), (X, Y), (Z, Z)])
    w1 = f.add_many([(t0, t0)])[0]
    w2 = f.add_many([(w1, w1)])[0]
    t2 = f.mul_many([(b3, zz)])[0]                     # 3b Z^2
    w3 = f.add_many([(w2, w2), (t0, t2), (t2, t2)])
    z3, y3 = w3[0], w3[1]                              # z3 = 8Y^2
    t2_3 = f.add_many([(w3[2], t2)])[0]                # 3 t2
    t0 = f.sub_many([(t0, t2_3)])[0]                   # Y^2 - 9b Z^2
    m1, m2, m3, m4 = f.mul_many([(t2, z3), (t1, z3), (t0, y3), (t0, xy)])
    fin = f.add_many([(m4, m4), (m1, m3)])
    return (fin[0], fin[1], m2)


def _identity_like(f, p):
    return (f.zero_like(p[0]), f.one_like(p[1]), f.zero_like(p[2]))


def _neg(f, p):
    return (p[0], f.neg(p[1]), p[2])


def _is_identity(f, p):
    return f.is_zero(p[2])


def _select(f, cond, p, q):
    return tuple(f.select(cond, a, b) for a, b in zip(p, q))


def _scalar_mul(f, p, bits):
    """[k]P via MSB-first double-and-add.

    ``bits``: static numpy bit array (shared exponent) or a traced
    ``(..., n)`` uint32 array (per-element scalars).  Doubling uses the
    dedicated 9-mul formula; for a static (unbatched) schedule the add
    hangs off ``lax.cond`` so zero bits pay nothing at runtime.
    """
    acc = _identity_like(f, p)
    bits = jnp.asarray(bits)
    if bits.ndim > 1:
        xs = jnp.moveaxis(bits, -1, 0)
        batched_bits = True
    else:
        xs = bits
        batched_bits = False

    def step(acc, bit):
        acc = _complete_dbl(f, acc)
        if batched_bits:
            nxt = _complete_add(f, acc, p)
            acc = _select(f, bit != 0, nxt, acc)
        else:
            acc = lax.cond(bit != 0,
                               lambda a: _complete_add(f, a, p),
                               lambda a: a, acc)
        return acc, None

    acc, _ = lax.scan(step, acc, xs)
    return acc


def _tree_sum(f, pts, axis=0):
    """Sum points over ``axis`` by halving reductions (log depth): each
    level adds the first half of the live prefix to the second, so it
    adds half as many points as the level before."""
    lead = (slice(None),) * axis
    n = jax.tree_util.tree_leaves(pts)[0].shape[axis]
    # pad to a power of two with identities (m - n < n always)
    m = 1 if n <= 1 else 1 << (n - 1).bit_length()
    if m != n:
        ident = _identity_like(f, pts)
        pts = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b[lead + (slice(m - n),)]],
                                         axis=axis), pts, ident)
    while m > 1:
        m //= 2
        lo = jax.tree_util.tree_map(lambda a: a[lead + (slice(m),)], pts)
        hi = jax.tree_util.tree_map(lambda a: a[lead + (slice(m, None),)], pts)
        pts = _complete_add(f, lo, hi)
    return jax.tree_util.tree_map(lambda a: a[lead + (0,)], pts)


def _to_affine_host(f, p):
    """Host-side: projective limb point -> oracle affine point (single)."""
    if f is _FqOps:
        zs = L.unpack_mont(p[2])[0]
        if zs == 0:
            return G1Point.inf()
        from consensus_specs_tpu.ops.bls12_381.fields import Fq
        x, y = L.unpack_mont(p[0])[0], L.unpack_mont(p[1])[0]
        zi = Fq(zs).inv()
        return G1Point(Fq(x) * zi, Fq(y) * zi)
    zs = (L.unpack_mont(p[2][0])[0], L.unpack_mont(p[2][1])[0])
    if zs == (0, 0):
        return G2Point.inf()
    x = _OFq2(L.unpack_mont(p[0][0])[0], L.unpack_mont(p[0][1])[0])
    y = _OFq2(L.unpack_mont(p[1][0])[0], L.unpack_mont(p[1][1])[0])
    zi = _OFq2(*zs).inv()
    return G2Point(x * zi, y * zi)


# ---------------------------------------------------------------------------
# Public G1/G2 API
# ---------------------------------------------------------------------------

def g1_add(p, q):
    return _complete_add(_FqOps, p, q)


def g1_dbl(p):
    return _complete_dbl(_FqOps, p)


def g2_dbl(p):
    return _complete_dbl(_Fq2Ops, p)


def g2_add(p, q):
    return _complete_add(_Fq2Ops, p, q)


def g1_neg(p):
    return _neg(_FqOps, p)


def g2_neg(p):
    return _neg(_Fq2Ops, p)


def g1_identity_like(p):
    return _identity_like(_FqOps, p)


def g2_identity_like(p):
    return _identity_like(_Fq2Ops, p)


def g1_scalar_mul(p, bits):
    return _scalar_mul(_FqOps, p, bits)


def g2_scalar_mul(p, bits):
    return _scalar_mul(_Fq2Ops, p, bits)


def g1_tree_sum(pts):
    return _tree_sum(_FqOps, pts)


def g1_tree_sum_batched(pts):
    """Sum over axis 1 of a (B, N, ...) packed batch, N a power of two.

    A halving tree: log2(N) complete adds of N/2, N/4, ..., 1 points a
    row, so the (B, N) grid is added once.  The module holds log2(N)
    differently-shaped adds, which costs compile time, not run time.
    """
    n = jax.tree_util.tree_leaves(pts)[0].shape[1]
    assert n & (n - 1) == 0, "pad the aggregation axis to a power of two"
    return _tree_sum(_FqOps, pts, axis=1)


def g2_tree_sum(pts):
    return _tree_sum(_Fq2Ops, pts)


def g1_is_identity(p):
    return _is_identity(_FqOps, p)


def g2_is_identity(p):
    return _is_identity(_Fq2Ops, p)


def g1_select(cond, p, q):
    return _select(_FqOps, cond, p, q)


def g2_select(cond, p, q):
    return _select(_Fq2Ops, cond, p, q)


def g1_normalize(p):
    """Projective -> affine-with-Z=1 (identity maps to (0, 1, 0))."""
    zinv = L.inv_mod(p[2])
    inf = L.is_zero(p[2])
    x = L.mont_mul(p[0], zinv)
    y = L.mont_mul(p[1], zinv)
    one = _FqOps.one_like(p[2])
    return (L.select(inf, jnp.zeros_like(x), x),
            L.select(inf, one, y),
            L.select(inf, jnp.zeros_like(p[2]), one))


def g2_normalize(p):
    zinv = T.f2_inv(p[2])
    inf = T.f2_is_zero(p[2])
    x = T.f2_mul(p[0], zinv)
    y = T.f2_mul(p[1], zinv)
    one = T.f2_one_like(p[2])
    zero = T.f2_zero_like(p[2])
    return (T.f2_select(inf, zero, x),
            T.f2_select(inf, one, y),
            T.f2_select(inf, zero, one))


# Staged normalizations: the field inversion dispatches through the
# shared ladder program (limbs._j_pow_windows) so only the cheap
# combine compiles per call site.  Same math as g1/g2_normalize.

@kjit
def _j_g1_norm_post(p, zinv):
    inf = L.is_zero(p[2])
    x = L.mont_mul(p[0], zinv)
    y = L.mont_mul(p[1], zinv)
    one = _FqOps.one_like(p[2])
    pt = (L.select(inf, jnp.zeros_like(x), x),
          L.select(inf, one, y),
          L.select(inf, jnp.zeros_like(p[2]), one))
    return pt, inf


def g1_normalize_flag_staged(p):
    """Projective -> affine-with-Z=1 + identity flag, staged."""
    zinv = L.pow_windows_staged(p[2], L.INV_WINDOWS)
    return _j_g1_norm_post(p, zinv)


@kjit
def _j_g2_norm_post(p, zinv):
    inf = T.f2_is_zero(p[2])
    x = T.f2_mul(p[0], zinv)
    y = T.f2_mul(p[1], zinv)
    one = T.f2_one_like(p[2])
    zero = T.f2_zero_like(p[2])
    return (T.f2_select(inf, zero, x),
            T.f2_select(inf, one, y),
            T.f2_select(inf, zero, one))


def g2_normalize_staged(p):
    return _j_g2_norm_post(p, T.staged_f2_inv(p[2]))


# ---------------------------------------------------------------------------
# Host-side packing: oracle points <-> limb pytrees
# ---------------------------------------------------------------------------

def g1_pack(points) -> tuple:
    """List of oracle G1Points -> batched projective limb point (N, 24)."""
    xs, ys, zs = [], [], []
    for pt in points:
        if pt.infinity:
            xs.append(0); ys.append(1); zs.append(0)
        else:
            xs.append(pt.x.n); ys.append(pt.y.n); zs.append(1)
    return (L.pack_ints_mont(xs), L.pack_ints_mont(ys), L.pack_ints_mont(zs))


def g2_pack(points) -> tuple:
    """List of oracle G2Points -> batched projective limb point."""
    coords = {k: [] for k in ("xa", "xb", "ya", "yb", "za", "zb")}
    for pt in points:
        if pt.infinity:
            vals = (0, 0, 1, 0, 0, 0)
        else:
            vals = (pt.x.a.n, pt.x.b.n, pt.y.a.n, pt.y.b.n, 1, 0)
        for k, v in zip(coords, vals):
            coords[k].append(v)
    pk = {k: L.pack_ints_mont(v) for k, v in coords.items()}
    return ((pk["xa"], pk["xb"]), (pk["ya"], pk["yb"]), (pk["za"], pk["zb"]))


def g1_unpack(p) -> G1Point:
    return _to_affine_host(_FqOps, p)


def g2_unpack(p) -> G2Point:
    return _to_affine_host(_Fq2Ops, p)
