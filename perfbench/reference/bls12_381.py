"""Plain BLS12-381 reference for the benchmark's output checks.

Independent of the program: python integers, affine decode, Jacobian
point arithmetic, RFC 9380 hash-to-G2 for the eth2 ciphersuite
(``BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_``).  No pairing: the
benchmark knows every secret key it signed with, so a signature set is
valid exactly when ``agg_pk == [s]G1`` and ``sig == [s]H(m)`` for the
set's summed secret key ``s`` (then ``e(agg_pk, H(m)) = e(G1, sig)``).

Field elements of Fp2 are tuples ``(c0, c1)`` meaning ``c0 + c1*u``
with ``u^2 = -1``.  Points are Jacobian tuples ``(X, Y, Z)``; ``None``
is the point at infinity.  The isogeny constants are RFC 9380 Appendix
E.3; the self-checks at the bottom fail on a wrong one.
"""
import hashlib

P = 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaaab
R = 0x73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001
X_PARAM = -0xd201000000010000          # the BLS parameter x (negative)
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"


# ---------------------------------------------------------------------------
# Fp and Fp2
# ---------------------------------------------------------------------------

class Fp:
    zero, one = 0, 1

    @staticmethod
    def add(a, b):
        return (a + b) % P

    @staticmethod
    def sub(a, b):
        return (a - b) % P

    @staticmethod
    def mul(a, b):
        return a * b % P

    @staticmethod
    def sqr(a):
        return a * a % P

    @staticmethod
    def neg(a):
        return -a % P

    @staticmethod
    def inv(a):
        return pow(a, -1, P)

    @staticmethod
    def small(a, k):
        return a * k % P


class Fp2:
    zero, one = (0, 0), (1, 0)

    @staticmethod
    def add(a, b):
        return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)

    @staticmethod
    def sub(a, b):
        return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)

    @staticmethod
    def mul(a, b):
        t0 = a[0] * b[0]
        t1 = a[1] * b[1]
        return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)

    @staticmethod
    def sqr(a):
        return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)

    @staticmethod
    def neg(a):
        return (-a[0] % P, -a[1] % P)

    @staticmethod
    def inv(a):
        d = pow(a[0] * a[0] + a[1] * a[1], -1, P)
        return (a[0] * d % P, -a[1] * d % P)

    @staticmethod
    def small(a, k):
        return (a[0] * k % P, a[1] * k % P)


def f2_pow(a, e):
    out = Fp2.one
    for bit in bin(e)[2:]:
        out = Fp2.sqr(out)
        if bit == "1":
            out = Fp2.mul(out, a)
    return out


def fp_sqrt(a):
    """Square root in Fp (p = 3 mod 4), or None."""
    y = pow(a, (P + 1) // 4, P)
    return y if y * y % P == a % P else None


def f2_sqrt(a):
    """Square root in Fp2 (p = 3 mod 4: the complex method), or None."""
    if a == Fp2.zero:
        return Fp2.zero
    a1 = f2_pow(a, (P - 3) // 4)
    alpha = Fp2.mul(Fp2.sqr(a1), a)
    x0 = Fp2.mul(a1, a)
    if alpha == (P - 1, 0):
        x = Fp2.mul((0, 1), x0)
    else:
        b = f2_pow(Fp2.add(Fp2.one, alpha), (P - 1) // 2)
        x = Fp2.mul(b, x0)
    return x if Fp2.sqr(x) == a else None


def f2_is_square(a):
    # a is a square in Fp2 iff its norm is a square in Fp
    n = (a[0] * a[0] + a[1] * a[1]) % P
    return n == 0 or pow(n, (P - 1) // 2, P) == 1


def sgn0_fp2(a):
    return a[0] % 2 if a[0] != 0 else a[1] % 2


# ---------------------------------------------------------------------------
# Curve arithmetic, generic over the field (a = 0 short Weierstrass)
# ---------------------------------------------------------------------------

B1 = 4
B2 = (4, 4)                               # 4 * (1 + u)


def dbl(F, p):
    if p is None:
        return None
    X, Y, Z = p
    if Y == F.zero:
        return None
    A = F.sqr(X)
    B = F.sqr(Y)
    C = F.sqr(B)
    D = F.small(F.sub(F.sub(F.sqr(F.add(X, B)), A), C), 2)
    E = F.small(A, 3)
    Fv = F.sqr(E)
    X3 = F.sub(Fv, F.small(D, 2))
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), F.small(C, 8))
    Z3 = F.small(F.mul(Y, Z), 2)
    return (X3, Y3, Z3)


def add(F, p, q):
    if p is None:
        return q
    if q is None:
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = F.sqr(Z1)
    Z2Z2 = F.sqr(Z2)
    U1 = F.mul(X1, Z2Z2)
    U2 = F.mul(X2, Z1Z1)
    S1 = F.mul(F.mul(Y1, Z2), Z2Z2)
    S2 = F.mul(F.mul(Y2, Z1), Z1Z1)
    H = F.sub(U2, U1)
    r = F.small(F.sub(S2, S1), 2)
    if H == F.zero:
        return dbl(F, p) if r == F.zero else None
    I = F.sqr(F.small(H, 2))
    J = F.mul(H, I)
    V = F.mul(U1, I)
    X3 = F.sub(F.sub(F.sqr(r), J), F.small(V, 2))
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), F.small(F.mul(S1, J), 2))
    Z3 = F.mul(F.sub(F.sub(F.sqr(F.add(Z1, Z2)), Z1Z1), Z2Z2), H)
    return (X3, Y3, Z3)


def neg(F, p):
    return None if p is None else (p[0], F.neg(p[1]), p[2])


def mul(F, p, k):
    """[k]p by double-and-add; k may be negative."""
    if k < 0:
        return mul(F, neg(F, p), -k)
    out = None
    for bit in bin(k)[2:] if k else "":
        out = dbl(F, out)
        if bit == "1":
            out = add(F, out, p)
    return out


def affine(F, p):
    """(x, y) or None."""
    if p is None:
        return None
    X, Y, Z = p
    zi = F.inv(Z)
    zi2 = F.sqr(zi)
    return (F.mul(X, zi2), F.mul(Y, F.mul(zi2, zi)))


def from_affine(F, a):
    return None if a is None else (a[0], a[1], F.one)


def on_curve(F, a, b):
    x, y = a
    return F.sqr(y) == F.add(F.mul(F.sqr(x), x), b)


G1 = (0x17f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac586c55e83ff97a1aeffb3af00adb22c6bb,
      0x08b3f481e3aaa0f1a09e30ed741d8ae4fcf5e095d5d00af600db18cb2c04b3edd03cc744a2888ae40caa232946c5e7e1)
G2 = ((0x024aa2b2f08f0a91260805272dc51051c6e47ad4fa403b02b4510b647ae3d1770bac0326a805bbefd48056c8c121bdb8,
       0x13e02b6052719f607dacd3a088274f65596bd0d09920b61ab5da61bbdc7f5049334cf11213945d57e5ac7d055d042b7e),
      (0x0ce5d527727d6e118cc9cdc6da2e351aadfd9baa8cbdd3a76d429a695160d12c923ac9cc3baca289e193548608b82801,
       0x0606c4a02ea734cc32acd2b02bc28b99cb3e287e85a763af267492ab572e99ab3f370d275cec1da1aaa9075ff05f79be))


# ---------------------------------------------------------------------------
# Compressed encodings (ZCash format)
# ---------------------------------------------------------------------------

def _flags(b0):
    return bool(b0 & 0x80), bool(b0 & 0x40), bool(b0 & 0x20)


def decode_g1(data: bytes):
    """bytes48 -> affine (x, y), None for infinity; raises on a bad
    encoding or a point off the curve."""
    data = bytes(data)
    c, inf, sign = _flags(data[0])
    if len(data) != 48 or not c:
        raise ValueError("not a compressed G1 point")
    x = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:], "big")
    if inf:
        if x or sign:
            raise ValueError("bad infinity encoding")
        return None
    if x >= P:
        raise ValueError("x not canonical")
    y = fp_sqrt((x * x * x + B1) % P)
    if y is None:
        raise ValueError("x not on G1")
    if (y > (P - 1) // 2) != sign:
        y = P - y
    return (x, y)


def encode_g1(a) -> bytes:
    if a is None:
        return bytes([0xC0]) + bytes(47)
    x, y = a
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if y > (P - 1) // 2 else 0)
    return bytes(out)


def _f2_larger(y):
    return y[1] > (P - 1) // 2 if y[1] else y[0] > (P - 1) // 2


def decode_g2(data: bytes):
    """bytes96 -> affine ((x0, x1), (y0, y1)), None for infinity."""
    data = bytes(data)
    c, inf, sign = _flags(data[0])
    if len(data) != 96 or not c:
        raise ValueError("not a compressed G2 point")
    x1 = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:48], "big")
    x0 = int.from_bytes(data[48:], "big")
    if inf:
        if x0 or x1 or sign:
            raise ValueError("bad infinity encoding")
        return None
    if x0 >= P or x1 >= P:
        raise ValueError("x not canonical")
    x = (x0, x1)
    y = f2_sqrt(Fp2.add(Fp2.mul(Fp2.sqr(x), x), B2))
    if y is None:
        raise ValueError("x not on G2")
    if _f2_larger(y) != sign:
        y = Fp2.neg(y)
    return (x, y)


# ---------------------------------------------------------------------------
# Hash to G2 (RFC 9380, suite BLS12381G2_XMD:SHA-256_SSWU_RO_)
# ---------------------------------------------------------------------------

def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    ell = (n + 31) // 32
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\0"
                        + dst_prime).digest()
    b = [hashlib.sha256(b0 + b"\1" + dst_prime).digest()]
    for i in range(2, ell + 1):
        x = bytes(s ^ t for s, t in zip(b0, b[-1]))
        b.append(hashlib.sha256(x + bytes([i]) + dst_prime).digest())
    return b"".join(b)[:n]


def hash_to_field(msg: bytes, dst: bytes = DST):
    """Two Fp2 elements (u0, u1)."""
    data = expand_message_xmd(msg, dst, 256)
    e = [int.from_bytes(data[64 * i:64 * (i + 1)], "big") % P
         for i in range(4)]
    return (e[0], e[1]), (e[2], e[3])


SSWU_A = (0, 240)
SSWU_B = (1012, 1012)
SSWU_Z = (P - 2, P - 1)                   # -(2 + u)


def map_to_curve_sswu(u):
    """RFC 9380 section 6.6.2 onto E': y^2 = x^3 + A'x + B'."""
    A, B, Z = SSWU_A, SSWU_B, SSWU_Z
    zu2 = Fp2.mul(Z, Fp2.sqr(u))
    tv = Fp2.add(Fp2.sqr(zu2), zu2)
    if tv == Fp2.zero:
        x1 = Fp2.mul(B, Fp2.inv(Fp2.mul(Z, A)))
    else:
        x1 = Fp2.mul(Fp2.mul(Fp2.neg(B), Fp2.inv(A)),
                     Fp2.add(Fp2.one, Fp2.inv(tv)))

    def g(x):
        return Fp2.add(Fp2.add(Fp2.mul(Fp2.sqr(x), x), Fp2.mul(A, x)), B)

    gx1 = g(x1)
    if f2_is_square(gx1):
        x, y = x1, f2_sqrt(gx1)
    else:
        x = Fp2.mul(zu2, x1)
        y = f2_sqrt(g(x))
    if sgn0_fp2(u) != sgn0_fp2(y):
        y = Fp2.neg(y)
    return x, y


# RFC 9380 Appendix E.3: 3-isogeny E' -> E2, coefficients low degree first
ISO_XNUM = (
    (0x5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97d6,
     0x5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97d6),
    (0, 0x11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71a),
    (0x11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71e,
     0x8ab05f8bdd54cde190937e76bc3e447cc27c3d6fbd7063fcd104635a790520c0a395554e5c6aaaa9354ffffffffe38d),
    (0x171d6541fa38ccfaed6dea691f5fb614cb14b4e7f4e810aa22d6108f142b85757098e38d0f671c7188e2aaaaaaaa5ed1, 0),
)
ISO_XDEN = (
    (0, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa63),
    (0xc, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa9f),
    (1, 0),
)
ISO_YNUM = (
    (0x1530477c7ab4113b59a4c18b076d11930f7da5d4a07f649bf54439d87d27e500fc8c25ebf8c92f6812cfc71c71c6d706,
     0x1530477c7ab4113b59a4c18b076d11930f7da5d4a07f649bf54439d87d27e500fc8c25ebf8c92f6812cfc71c71c6d706),
    (0, 0x5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97be),
    (0x11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71c,
     0x8ab05f8bdd54cde190937e76bc3e447cc27c3d6fbd7063fcd104635a790520c0a395554e5c6aaaa9354ffffffffe38f),
    (0x124c9ad43b6cf79bfbf7043de3811ad0761b0f37a1e26286b0e977c69aa274524e79097a56dc4bd9e1b371c71c718b10, 0),
)
ISO_YDEN = (
    (0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa8fb,
     0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa8fb),
    (0, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa9d3),
    (0x12, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa99),
    (1, 0),
)


def _horner(coeffs, x):
    acc = Fp2.zero
    for c in reversed(coeffs):
        acc = Fp2.add(Fp2.mul(acc, x), c)
    return acc


def iso_map(x, y):
    xn, xd = _horner(ISO_XNUM, x), _horner(ISO_XDEN, x)
    yn, yd = _horner(ISO_YNUM, x), _horner(ISO_YDEN, x)
    return (Fp2.mul(xn, Fp2.inv(xd)), Fp2.mul(y, Fp2.mul(yn, Fp2.inv(yd))))


# psi = untwist-Frobenius-twist; Frobenius on Fp2 is conjugation
_PSI_CX = Fp2.inv(f2_pow((1, 1), (P - 1) // 3))
_PSI_CY = Fp2.inv(f2_pow((1, 1), (P - 1) // 2))


def _conj(a):
    return (a[0], -a[1] % P)


def psi(p):
    """psi on a Jacobian point: (X, Y, Z) -> (c_x X^p, c_y Y^p, Z^p)."""
    if p is None:
        return None
    X, Y, Z = p
    return (Fp2.mul(_conj(X), _PSI_CX), Fp2.mul(_conj(Y), _PSI_CY), _conj(Z))


def clear_cofactor(p):
    """[h_eff]P by RFC 9380 section 8.8.2 (Budroni-Pintore):
    [x^2 - x - 1]P + [x - 1]psi(P) + psi^2([2]P)."""
    x = X_PARAM
    t1 = mul(Fp2, p, x * x - x - 1)
    t2 = mul(Fp2, psi(p), x - 1)
    t3 = psi(psi(dbl(Fp2, p)))
    return add(Fp2, add(Fp2, t1, t2), t3)


def hash_to_g2(msg: bytes, dst: bytes = DST):
    """Jacobian H(msg) in G2."""
    u0, u1 = hash_to_field(msg, dst)
    q0 = from_affine(Fp2, iso_map(*map_to_curve_sswu(u0)))
    q1 = from_affine(Fp2, iso_map(*map_to_curve_sswu(u1)))
    return clear_cofactor(add(Fp2, q0, q1))


# ---------------------------------------------------------------------------
# Self-checks: a wrong constant fails here, at import
# ---------------------------------------------------------------------------

def _self_check():
    assert on_curve(Fp, G1, B1) and on_curve(Fp2, G2, B2)
    assert mul(Fp, from_affine(Fp, G1), R) is None
    assert mul(Fp2, from_affine(Fp2, G2), R) is None
    g2 = from_affine(Fp2, G2)
    assert affine(Fp2, psi(g2)) == affine(Fp2, mul(Fp2, g2, P % R))
    h = hash_to_g2(b"perfbench reference self-check")
    assert on_curve(Fp2, affine(Fp2, h), B2)
    assert mul(Fp2, h, R) is None and h is not None
    assert decode_g1(encode_g1(G1)) == G1


_self_check()
