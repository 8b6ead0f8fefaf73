"""The plain reference against second witnesses: the program's native C
library and its python oracle agree with it on encodings, hash-to-G2 and
signatures.  (The reference itself imports nothing of the program.)"""
import pytest

from reference import bls12_381 as RB
from reference import rlc as RLC


@pytest.fixture(scope="module")
def native():
    from consensus_specs_tpu.ops import native_bls
    if not native_bls.available():
        pytest.skip("native BLS library unavailable")
    return native_bls


def test_pubkeys_match_native(native):
    for sk in (1, 2, 77, 8192, RB.R - 5):
        ref = RB.encode_g1(RB.affine(RB.Fp, RB.mul(
            RB.Fp, RB.from_affine(RB.Fp, RB.G1), sk)))
        assert ref == native.SkToPk(sk)


def test_hash_to_g2_matches_oracle():
    from consensus_specs_tpu.ops.bls12_381 import hash_to_curve as oracle
    for msg in (b"", b"abc", bytes(range(32))):
        got = RB.affine(RB.Fp2, RB.hash_to_g2(msg))
        want = oracle.hash_to_g2(msg)
        assert got == ((want.x.a.n, want.x.b.n), (want.y.a.n, want.y.b.n))


def test_signature_is_secret_times_hash(native):
    msg = b"perfbench".ljust(32, b"\0")
    sk = 123456789
    sig = RB.decode_g2(native.Sign(sk, msg))
    assert sig == RB.affine(RB.Fp2, RB.mul(RB.Fp2, RB.hash_to_g2(msg), sk))


def test_decode_rejects_bad_encodings():
    with pytest.raises(ValueError):
        RB.decode_g1(bytes(48))                 # compression flag unset
    with pytest.raises(ValueError):
        RB.decode_g2(b"\x80" + b"\xff" * 95)    # x not canonical
    assert RB.decode_g1(b"\xc0" + bytes(47)) is None


def test_fold_validity(native):
    sks = [3, 5, 7]
    pks = [native.SkToPk(s) for s in sks]
    msg = b"m".ljust(32, b"\0")
    good = (pks, msg, native.Sign(sum(sks), msg))
    bad = (pks, msg, native.Sign(sum(sks) + 1, msg))
    ref = RLC.fold([good, bad], [sum(sks), sum(sks)])
    assert ref["valid"] == [True, False]
    assert len(ref["agg"]) == len(ref["h"]) == 2 and ref["sig_sum"]


def test_scalars_match_program():
    from consensus_specs_tpu.ops import bls_rlc
    items = [([b"\x01" * 48, b"\x02" * 48], b"m" * 32, b"\x03" * 96),
             ([b"\x04" * 48], b"n" * 32, b"\x05" * 96)]
    assert RLC.derive_scalars(items) == bls_rlc.derive_scalars(items)
