"""Differential tests: JAX BLS12-381 kernels vs the pure-python oracle.

Fast tier (always on): Fq limb arithmetic, Fq2/Fq12 towers, G1/G2 complete
point formulas - each jit compiles in seconds.

Heavy tier (set ``CS_TPU_HEAVY=1``): full pairing bilinearity and the
end-to-end ``bls.use_jax()`` backend - the pairing program takes minutes to
compile cold on the 1-core CI box (cached in ``.jax_cache`` afterwards).
"""
import random

import numpy as np
import pytest
import jax

from consensus_specs_tpu.ops.bls12_381.fields import P, R_ORDER, Fq2, Fq6, Fq12
from consensus_specs_tpu.ops.bls12_381.curve import (
    G1_GENERATOR, G2_GENERATOR, G1Point)
from consensus_specs_tpu.ops.jax_bls import limbs as L
from consensus_specs_tpu.ops.jax_bls import tower as T
from consensus_specs_tpu.ops.jax_bls import points as PT

from consensus_specs_tpu.utils.env_flags import HEAVY  # noqa: E402
rng = random.Random(1234)


def rand_fq():
    return rng.randrange(P)


def rand_fq2():
    return Fq2(rand_fq(), rand_fq())


def test_limb_roundtrip():
    for v in (0, 1, P - 1, rand_fq()):
        assert L.limbs_to_int(L.int_to_limbs(v)) == v


def test_limb_field_ops_match_python():
    xs = [rand_fq() for _ in range(6)] + [0, 1, P - 1]
    ys = [rand_fq() for _ in range(6)] + [P - 1, 0, 1]
    A, B = L.pack_ints_mont(xs), L.pack_ints_mont(ys)
    assert L.unpack_mont(L.mont_mul(A, B)) == [x * y % P for x, y in zip(xs, ys)]
    assert L.unpack_mont(L.add_mod(A, B)) == [(x + y) % P for x, y in zip(xs, ys)]
    assert L.unpack_mont(L.sub_mod(A, B)) == [(x - y) % P for x, y in zip(xs, ys)]
    assert L.unpack_mont(L.inv_mod(A)) == [pow(x, -1, P) if x else 0 for x in xs]


def test_fq2_ops_match_oracle():
    x, y = rand_fq2(), rand_fq2()
    X, Y = T.f2_const(x), T.f2_const(y)

    @jax.jit
    def suite(X, Y):
        return T.f2_mul(X, Y), T.f2_inv(X), T.f2_sqr(X), T.f2_mul_xi(X)

    mul, inv, sqr, xi = suite(X, Y)

    def to_oracle(p):
        return Fq2(L.unpack_mont(p[0])[0], L.unpack_mont(p[1])[0])

    assert to_oracle(mul) == x * y
    assert to_oracle(inv) == x.inv()
    assert to_oracle(sqr) == x.square()
    assert to_oracle(xi) == x * Fq2(1, 1)


@pytest.mark.skipif(not HEAVY, reason="sqrt program jit: set CS_TPU_HEAVY=1 (covered by the heavy hash-to-curve tier)")
def test_fq2_sqrt_of_square():
    x = rand_fq2()
    s = x.square()
    r = jax.jit(T.f2_sqrt)(T.f2_const(s))
    rr = Fq2(L.unpack_mont(r[0])[0], L.unpack_mont(r[1])[0])
    assert rr.square() == s
    assert bool(np.asarray(jax.jit(T.f2_is_square)(T.f2_const(s))))


def test_fq12_mul_matches_oracle():
    def rf6():
        return Fq6(rand_fq2(), rand_fq2(), rand_fq2())
    x, y = Fq12(rf6(), rf6()), Fq12(rf6(), rf6())
    got = jax.jit(T.f12_mul)(T.f12_const(x), T.f12_const(y))
    assert T.f12_to_oracle(got) == x * y


def test_g1_complete_add_matches_oracle():
    ks = [rng.randrange(1, R_ORDER) for _ in range(4)]
    pts = [G1_GENERATOR.mult(k) for k in ks]
    pts[2] = G1Point.inf()  # identity handling
    packed = PT.g1_pack(pts)
    flipped = jax.tree_util.tree_map(lambda a: a[::-1].copy(), packed)
    out = jax.jit(PT.g1_add)(packed, flipped)
    for i in range(4):
        got = PT.g1_unpack(jax.tree_util.tree_map(lambda a: a[i], out))
        assert got == pts[i] + pts[3 - i]


def test_g1_tree_sum_matches_oracle():
    ks = [rng.randrange(1, R_ORDER) for _ in range(5)]  # odd: exercises pad
    pts = [G1_GENERATOR.mult(k) for k in ks]
    got = PT.g1_unpack(jax.jit(PT.g1_tree_sum)(PT.g1_pack(pts)))
    exp = G1Point.inf()
    for p in pts:
        exp = exp + p
    assert got == exp


def _tree_sum_rows(n):
    """Four rows of ``n`` points: live points over identity padding at
    the tail, all identity, P with -P, and one point twice.  The partner
    sits n/2 along, so the first halving level adds P to -P (a sum that
    is the identity) and Q to Q (the doubling case of the complete add)."""
    r = random.Random(n)

    def live(k):
        return [G1_GENERATOR.mult(r.randrange(1, R_ORDER)) for _ in range(k)]

    padded = live(max(1, n - 3))
    rows = [padded + [G1Point.inf()] * (n - len(padded)),
            [G1Point.inf()] * n, live(n), live(n)]
    if n > 1:
        rows[2][n // 2] = -rows[2][0]
        rows[3][n // 2] = rows[3][0]
    return rows


def _check_g1_tree_sum_batched(n):
    """``PT.g1_tree_sum_batched`` over a (4, n) grid, row by row against
    the oracle's sum; jitted, or eager on the numpy mirror."""
    from consensus_specs_tpu.ops.jax_bls.backend import kjit
    rows = _tree_sum_rows(n)
    packed = PT.g1_pack([p for row in rows for p in row])
    grid = jax.tree_util.tree_map(
        lambda a: a.reshape(len(rows), n, L.NLIMB), packed)
    out = kjit(PT.g1_tree_sum_batched)(grid)
    for i, row in enumerate(rows):
        exp = G1Point.inf()
        for p in row:
            exp = exp + p
        got = PT.g1_unpack(jax.tree_util.tree_map(lambda a: a[i], out))
        assert got == exp, (n, i)


_NUMPY_TREE_SUM_CHECK = r"""
import sys
sys.path[:0] = [%(repo)r, %(tests)r]
from consensus_specs_tpu.ops.jax_bls.backend import NUMPY_KERNELS
assert NUMPY_KERNELS
import test_jax_bls
test_jax_bls._check_g1_tree_sum_batched(%(n)d)
print("NUMPY-TREE-SUM-OK")
"""


@pytest.mark.parametrize("n", [1, 2, 8, 64])
@pytest.mark.parametrize("kernels", ["jax", "numpy"])
def test_g1_tree_sum_batched_matches_oracle(kernels, n):
    """The batched pubkey tree sum at B = 4: jitted here, and on the
    numpy mirror in a subprocess (the switch is import-time)."""
    if kernels == "jax":
        _check_g1_tree_sum_batched(n)
        return
    import os
    import subprocess
    import sys
    tests = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(tests)
    proc = subprocess.run(
        [sys.executable, "-c",
         _NUMPY_TREE_SUM_CHECK % {"repo": repo, "tests": tests, "n": n}],
        env=dict(os.environ, CS_TPU_NUMPY_KERNELS="1"),
        capture_output=True, timeout=300, cwd=repo)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    assert b"NUMPY-TREE-SUM-OK" in proc.stdout


def test_g2_scalar_mul_matches_oracle():
    k = 98765
    bits = np.array([int(c) for c in bin(k)[2:]], dtype=np.uint32)
    q = G2_GENERATOR.mult(321)
    got = PT.g2_unpack(jax.jit(
        lambda p: PT.g2_scalar_mul(p, bits))(PT.g2_pack([q])))
    # leading batch axis of 1
    assert got == q.mult(k)


# ---------------------------------------------------------------------------
# Heavy tier
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not HEAVY, reason="set CS_TPU_HEAVY=1 (cold compile is minutes)")
def test_pairing_bilinearity():
    """Bilinearity through the STAGED pipeline - the production path.

    The monolithic ``jax.jit(pairing_check)`` cannot compile on a weak
    XLA:CPU host (LLVM out-of-memory after ~40 min; measured round 4),
    so this exercises the same math as the pipeline of bounded programs
    the real verification path dispatches.  Inputs carry a (pairs,
    batch=1) shape; the lane bucket pads the batch axis internally.
    """
    import numpy as np
    import jax.numpy as jnp
    from consensus_specs_tpu.ops.jax_bls import pairing as PR

    a = rng.randrange(2, R_ORDER)

    def staged_check(pairs):
        g1 = PT.g1_pack([p for p, _ in pairs])
        g2 = PT.g2_pack([q for _, q in pairs])
        degen = jnp.array([[p.infinity or q.infinity] for p, q in pairs])
        px = g1[0][:, None]
        py = g1[1][:, None]
        q = ((g2[0][0][:, None], g2[0][1][:, None]),
             (g2[1][0][:, None], g2[1][1][:, None]))
        out = np.asarray(PR.staged_pairing_check(px, py, q, degen))
        return bool(out[0])

    assert staged_check([(G1_GENERATOR, G2_GENERATOR),
                         (-G1_GENERATOR, G2_GENERATOR)])
    assert staged_check([(G1_GENERATOR.mult(a), G2_GENERATOR),
                         (G1_GENERATOR, -(G2_GENERATOR.mult(a)))])
    assert not staged_check([(G1_GENERATOR.mult(a), G2_GENERATOR),
                             (G1_GENERATOR, G2_GENERATOR)])


@pytest.mark.skipif(not HEAVY, reason="set CS_TPU_HEAVY=1 (cold compile is minutes)")
def test_jax_backend_matches_py():
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.ops import bls_jax

    bls.use_py()
    pks = [bls.SkToPk(i) for i in (1, 2, 3)]
    msg = b"backend-parity"
    agg = bls.Aggregate([bls.Sign(i, msg) for i in (1, 2, 3)])
    assert bls.FastAggregateVerify(pks, msg, agg)
    out = bls_jax.verify_aggregates_batch([
        (pks, msg, agg),
        (pks, b"wrong", agg),
        ([pks[0]], msg, bls.Sign(1, msg)),
    ])
    assert out == [True, False, True]
    # infinity pubkey rejected per KeyValidate
    assert not bls_jax.FastAggregateVerify(
        [pks[0], b"\xc0" + b"\x00" * 47], msg, agg)
