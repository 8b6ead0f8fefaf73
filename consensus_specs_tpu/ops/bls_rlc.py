"""Random-linear-combination (RLC) whole-batch BLS verification.

The one-pairing-per-block engine behind ``utils/bls.DeferredBatch.flush``
(``CS_TPU_BLS_RLC``, default on).  Each queued assert-style check i says

    e(agg_pk_i, H(m_i)) * e(-G1, sig_i) == 1.

Draw independent nonzero 128-bit scalars r_i and verify the single folded
product instead::

    prod_i e([r_i] agg_pk_i, H(m_i)) * e(-G1, sum_i [r_i] sig_i)
          * prod_k prod_j e([r_k] P_kj, Q_kj)  == 1

(the trailing factor folds deferred *raw* pairing-product checks such as
the Deneb blob-KZG batch, each with its own scalar r_k).  By bilinearity
a batch of all-valid items always passes; a batch containing any invalid
item passes with probability <= 2^-128 over the scalar draw (the checks'
pairing values generate a cyclic group of order r, and a nontrivial
combination must hit the identity).  The work collapses to one G2 MSM
over the signatures, one batched G1 aggregate+scale over the pubkeys,
hash-to-curve, and ONE product pairing check (one final exponentiation)
- versus one full pairing check per item on the per-lane path.

Scalars are seeded deterministically (Fiat-Shamir style) from a SHA-256
hash of the queued tuples, so scripted runs and replays are bit-for-bit
reproducible; fixing the batch fixes the scalars, but any *change* to a
queued item re-randomizes every coefficient, so an adversary cannot
steer a forged batch toward a passing combination.

Failure semantics live in the caller: a ``False`` combined verdict (or a
``None`` = structurally invalid item: bad encoding, out-of-subgroup
point, infinity pubkey, empty pubkey list) makes ``flush`` re-run the
per-lane path to bisect and report exactly which item failed.

Backends: the python oracle, the native C library (streaming product
pairing in ``csrc/bls12_381.c``), and the JAX device path
(``ops/bls_jax.rlc_pubkey_phase``, launched before the signatures are
decoded, then ``rlc_signature_phase``), which lowers the signature MSM
onto the points-sharded mesh program of
``parallel/sharded_verify.make_sharded_g2_msm`` when a mesh has been
registered via :func:`use_mesh`.
"""
import hashlib

from consensus_specs_tpu.ops.bls12_381.curve import (
    G1Point, g1_from_compressed, msm)
from consensus_specs_tpu.ops.bls12_381 import ciphersuite as _oracle
from consensus_specs_tpu.ops.bls12_381.fields import R_ORDER
from consensus_specs_tpu.ops.bls12_381.hash_to_curve import hash_to_g2, DST_G2
from consensus_specs_tpu.ops.bls12_381.pairing import multi_pairing_check
from consensus_specs_tpu import supervisor
from consensus_specs_tpu.obs import registry
from consensus_specs_tpu.obs.tracing import span

SCALAR_BITS = 128
_DOMAIN = b"CS_TPU_BLS_RLC_V1"
_NEG_G1 = None      # lazy: -G1_GENERATOR and its compressed form
_NEG_G1_C = None


def _neg_g1():
    global _NEG_G1, _NEG_G1_C
    if _NEG_G1 is None:
        from consensus_specs_tpu.ops.bls12_381.curve import G1_GENERATOR
        _NEG_G1 = -G1_GENERATOR
        _NEG_G1_C = _NEG_G1.to_compressed()
    return _NEG_G1, _NEG_G1_C


# ---------------------------------------------------------------------------
# Deterministic scalar derivation
# ---------------------------------------------------------------------------

def _u64(n: int) -> bytes:
    return int(n).to_bytes(8, "little")


def derive_scalars(items, extra_checks=()) -> list:
    """Per-check 128-bit nonzero coefficients, seeded from a hash of the
    whole queue: ``len(items) + len(extra_checks)`` scalars, items first.

    Deterministic by design (reproducible replays); every byte of every
    queued tuple feeds the seed, so no queued value can be chosen as a
    function of its own coefficient.
    """
    h = hashlib.sha256(_DOMAIN)
    h.update(_u64(len(items)))
    for pubkeys, message, signature in items:
        h.update(_u64(len(pubkeys)))
        for pk in pubkeys:
            h.update(bytes(pk))
        h.update(_u64(len(message)))
        h.update(bytes(message))
        h.update(bytes(signature))
    h.update(_u64(len(extra_checks)))
    for pairs, label in extra_checks:
        lb = label.encode() if isinstance(label, str) else bytes(label)
        h.update(_u64(len(lb)))
        h.update(lb)
        h.update(_u64(len(pairs)))
        for p, q in pairs:
            h.update(p.to_compressed())
            h.update(q.to_compressed())
    seed = h.digest()
    out = []
    for i in range(len(items) + len(extra_checks)):
        r = int.from_bytes(
            hashlib.sha256(seed + _u64(i)).digest()[:SCALAR_BITS // 8],
            "little")
        out.append(r if r else 1)
    return out


# ---------------------------------------------------------------------------
# Optional device mesh for the signature MSM (jax backend only)
# ---------------------------------------------------------------------------

_MESH_DEVICES = None


def use_mesh(devices) -> None:
    """Register a 1D device tuple: the jax-path signature MSM shards its
    point axis across it (``parallel.sharded_verify`` — any batch size,
    uneven shards padded with identity lanes).  Pass ``"auto"`` to
    derive the mesh shape from ``jax.devices()`` live at every flush
    (the serving-deployment mode: nothing hardcodes a device count);
    pass ``None`` to go back to the single-device program."""
    global _MESH_DEVICES
    if devices == "auto":
        _MESH_DEVICES = "auto"
    else:
        _MESH_DEVICES = tuple(devices) if devices else None


def mesh_devices():
    if _MESH_DEVICES == "auto":
        import jax
        devs = tuple(jax.devices())
        return devs if len(devs) > 1 else None
    return _MESH_DEVICES


# ---------------------------------------------------------------------------
# Backend combiners.  Each returns True/False for the folded product, or
# None when an item is structurally invalid (caller bisects).
# ---------------------------------------------------------------------------

def _scale_g1_host(p: G1Point, r: int) -> G1Point:
    """[r]P for a handful of host-side oracle points (the deferred raw
    pairs), through the native library when present."""
    if p.infinity or r % R_ORDER == 0:
        return G1Point.inf()
    try:
        from consensus_specs_tpu.ops import native_bls
        if native_bls.available():
            return g1_from_compressed(
                native_bls.g1_msm_affine([(p.x.n, p.y.n)], [r]))
    except Exception:
        pass
    return p.mult(r)


def _check_py(items, extra_checks, scalars):
    n = len(items)
    pairs = []
    sig_pts, sig_rs = [], []
    for (pubkeys, message, signature), r in zip(items, scalars):
        if not pubkeys:
            return None
        agg = G1Point.inf()
        for pk in pubkeys:
            pt = _oracle._decode_pubkey(bytes(pk))
            if pt is None:
                return None
            agg = agg + pt
        try:
            spt = _oracle._decode_sig(bytes(signature))
        except Exception:
            return None
        pairs.append((agg.mult(r), hash_to_g2(bytes(message))))
        sig_pts.append(spt)
        sig_rs.append(r)
    if sig_pts:
        pairs.append((_neg_g1()[0], msm(sig_pts, sig_rs)))
    for (chk_pairs, _label), r in zip(extra_checks, scalars[n:]):
        for p, q in chk_pairs:
            pairs.append((_scale_g1_host(p, r), q))
    if not pairs:
        return True
    return multi_pairing_check(pairs)


def _check_native(items, extra_checks, scalars):
    from consensus_specs_tpu.ops import native_bls as nb
    n = len(items)
    g1s, g2s = [], []
    sig_bytes, sig_rs = [], []
    try:
        for (pubkeys, message, signature), r in zip(items, scalars):
            if not pubkeys:
                return None
            signature = bytes(signature)
            if not nb.g2_validate(signature):
                return None
            # AggregatePKs KeyValidates every pubkey (raises on invalid)
            agg = nb.AggregatePKs([bytes(pk) for pk in pubkeys])
            g1s.append(nb.g1_msm_compressed([agg], [r]))
            g2s.append(nb.hash_to_g2_compressed(bytes(message), DST_G2))
            sig_bytes.append(signature)
            sig_rs.append(r)
    except ValueError:
        return None
    if sig_bytes:
        g1s.append(_neg_g1()[1])
        g2s.append(nb.g2_msm_compressed(sig_bytes, sig_rs))
    for (chk_pairs, _label), r in zip(extra_checks, scalars[n:]):
        for p, q in chk_pairs:
            g1s.append(_scale_g1_host(p, r).to_compressed())
            g2s.append(q.to_compressed())
    if not g1s:
        return True
    return nb.pairing_check_compressed(g1s, g2s)


# jax-backend flushes whose pubkey phase launched before the
# signatures were decoded: ``used``, or ``discarded`` by an invalid one
_EARLY_DISPATCH = registry.counter("bls.rlc.early_dispatch")
_EARLY_USED = _EARLY_DISPATCH.labels(outcome="used")
_EARLY_DISCARDED = _EARLY_DISPATCH.labels(outcome="discarded")


def _check_jax(items, extra_checks, scalars):
    from consensus_specs_tpu.ops import bls_jax
    n = len(items)
    with span("bls.stage.pubkey_rows"):
        if any(not pubkeys for pubkeys, _, _ in items):
            return None
        pk_slots = bls_jax._slot_rows([pubkeys for pubkeys, _, _ in items])
        if None in pk_slots:
            return None
    # the pubkey side reads no signature: its device programs run while
    # the host decodes the signatures below
    pk_side = bls_jax.rlc_pubkey_phase(pk_slots, scalars[:n])
    sig_pts = []
    with span("bls.stage.sig_decompress"):
        for _, _, signature in items:
            spt = bls_jax._decompress_g2(signature)
            if spt is None:
                _EARLY_DISCARDED.add()
                return None
            sig_pts.append(spt)
    if pk_side is not None:
        _EARLY_USED.add()
    extra_pairs = []
    for (chk_pairs, _label), r in zip(extra_checks, scalars[n:]):
        for p, q in chk_pairs:
            extra_pairs.append((_scale_g1_host(p, r), q))
    if not pk_slots and not extra_pairs:
        return True
    return bls_jax.rlc_signature_phase(
        pk_side, [bytes(message) for _, message, _ in items], sig_pts,
        extra_pairs=extra_pairs, mesh_devices=mesh_devices())


_COMBINERS = {"py": _check_py, "native": _check_native, "jax": _check_jax}


def combined_check(items, extra_checks, backend_name: str):
    """Fold the whole queue into one product pairing and evaluate it.

    ``items``: [(pubkeys, message, signature)] byte triples;
    ``extra_checks``: [(pairs, label)] deferred raw pairing-product
    checks over oracle points.  Returns the combined verdict, or None
    when any item is structurally invalid - the caller then re-runs the
    per-lane path to report per-item results.
    """
    with span("bls.rlc.combine"):
        with span("bls.rlc.scalars"):
            scalars = derive_scalars(items, extra_checks)
        # cooperative deadline boundary between the (cheap) Fiat-Shamir
        # scalar stage and the MSM + pairing stage: an armed per-dispatch
        # budget (supervisor.deadline_scope in DeferredBatch.flush)
        # converts a pathologically slow flush into a counted
        # reason=deadline fallback onto the per-lane path
        supervisor.deadline_check()
        combine = _COMBINERS.get(backend_name, _check_py)
        return combine(items, extra_checks, scalars)
