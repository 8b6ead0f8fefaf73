"""Plain reference of the random-linear-combination (RLC) fold.

The program verifies a flushed batch as ONE product pairing
``prod_i e([r_i] agg_pk_i, H(m_i)) * e(-G1, sum_i [r_i] sig_i) == 1``
with 128-bit scalars ``r_i`` derived Fiat-Shamir style from the queued
items (``ops/bls_rlc.derive_scalars``, copied here as the protocol
definition so the benchmark's yardstick cannot move).  This module
computes, from the input bytes alone, the points that product pairs:
``[r_i] agg_pk_i``, ``H(m_i)`` and ``sum_i [r_i] sig_i``, in affine
coordinates, plus each set's validity from the secret keys.
"""
import hashlib

from . import bls12_381 as B

DOMAIN = b"CS_TPU_BLS_RLC_V1"


def _u64(n):
    return int(n).to_bytes(8, "little")


def derive_scalars(items, bits=128):
    """Per-item nonzero ``bits``-bit coefficients seeded from a hash of
    the whole queue (no deferred raw pairing checks here)."""
    h = hashlib.sha256(DOMAIN)
    h.update(_u64(len(items)))
    for pubkeys, message, signature in items:
        h.update(_u64(len(pubkeys)))
        for pk in pubkeys:
            h.update(bytes(pk))
        h.update(_u64(len(message)))
        h.update(bytes(message))
        h.update(bytes(signature))
    h.update(_u64(0))
    seed = h.digest()
    out = []
    for i in range(len(items)):
        r = int.from_bytes(hashlib.sha256(seed + _u64(i)).digest()[:bits // 8],
                           "little")
        out.append(r if r else 1)
    return out


class PubkeyCache:
    """Decoded pubkeys, shared across the flushes one run checks."""

    def __init__(self):
        self._pts = {}

    def __call__(self, pk):
        pt = self._pts.get(pk)
        if pt is None:
            pt = self._pts[pk] = B.from_affine(B.Fp, B.decode_g1(pk))
        return pt


def fold(items, secret_sums, pubkeys=None, scalar_bits=128):
    """The RLC pairing inputs of one flush and each set's validity.

    ``items``: [(pubkeys, message, signature)] byte triples in queue
    order; ``secret_sums``: per item, the sum of its members' secret
    keys mod r.  Returns ``{"agg": [affine [r_i] agg_pk_i], "h": [affine
    H(m_i)], "sig_sum": affine sum_i [r_i] sig_i, "valid": [bool]}``.
    """
    pubkeys = pubkeys or PubkeyCache()
    rs = derive_scalars(items, scalar_bits)
    g1 = B.from_affine(B.Fp, B.G1)
    agg_out, h_out, valid = [], [], []
    sig_sum = None
    for (pks, msg, sig), s, r in zip(items, secret_sums, rs):
        agg = None
        for pk in pks:
            agg = B.add(B.Fp, agg, pubkeys(bytes(pk)))
        h = B.hash_to_g2(bytes(msg))
        sig_pt = B.from_affine(B.Fp2, B.decode_g2(sig))
        valid.append(
            B.affine(B.Fp, agg) == B.affine(B.Fp, B.mul(B.Fp, g1, s))
            and B.affine(B.Fp2, sig_pt) == B.affine(B.Fp2, B.mul(B.Fp2, h, s)))
        agg_out.append(B.affine(B.Fp, B.mul(B.Fp, agg, r)))
        h_out.append(B.affine(B.Fp2, h))
        sig_sum = B.add(B.Fp2, sig_sum, B.mul(B.Fp2, sig_pt, r))
    return {"agg": agg_out, "h": h_out,
            "sig_sum": B.affine(B.Fp2, sig_sum), "valid": valid}
