"""DAS engine dispatch: live switch, counted fallbacks, supervision.

Wraps the eip7594 spec surface from outside (``install_das_accel``,
applied to both ladders by ``forks.install_engines`` — the markdown
bodies stay spec-shaped):

``verify_cell_proof_batch`` -> :func:`kernels.verify_cell_proof_batch`
    the whole batch in ONE product pairing check (zero own pairings
    inside an RLC scope), spec loop (one pairing per cell) on fallback.
``recover_polynomial`` -> :func:`kernels.recover_cells_batch`
    single-blob entry of the batched recovery; :func:`recover_many`
    exposes the genuinely multi-blob path (shared vanishing polynomial
    + batch inversion across blobs missing the same columns).

Contract (the PR-8/PR-9 engine contract, applied to the new sites
``das.verify`` / ``das.recover``):

* ``faults.check`` first — an injected fault degrades to the spec loop
  and books ``das.fallbacks{reason=injected}``; organic declines book
  ``reason=guard``; a mid-work ``DeadlineExceeded`` books
  ``reason=deadline``.
* ``supervisor.admit`` gates the attempt (an open breaker skips the
  engine), successes feed ``note_success``, every counted fallback
  feeds the breaker via the ``faults.count_fallback`` hook.
* ``supervisor.audit_due`` sentinel audits replay the call through the
  spec body under ``supervisor.probe()`` — the spec answer is
  authoritative; a mismatch quarantines the site.
* ``faults.corrupt_armed`` silent-corruption hooks: a corrupted verify
  flips the verdict, a corrupted recovery perturbs the first missing
  evaluation — what the sentinel audits exist to catch.

Metrics: ``das.verify{path=engine|spec}``,
``das.recover{path=engine|spec}``, ``das.fallbacks{reason=...}``,
``das.cells{op=verified|recovered}`` (docs/observability.md catalog).
"""
import functools

from consensus_specs_tpu import faults, supervisor
from consensus_specs_tpu.obs import registry as _obs
from consensus_specs_tpu.utils import env_flags as _env_flags

SITE_VERIFY = "das.verify"
SITE_RECOVER = "das.recover"

_C_VERIFY = {path: _obs.counter("das.verify").labels(path=path)
             for path in ("engine", "spec")}
_C_RECOVER = {path: _obs.counter("das.recover").labels(path=path)
              for path in ("engine", "spec")}
_C_FALLBACKS = {reason: _obs.counter("das.fallbacks").labels(reason=reason)
                for reason in ("guard", "injected", "deadline")}
_C_CELLS = {op: _obs.counter("das.cells").labels(op=op)
            for op in ("verified", "recovered")}


def enabled() -> bool:
    """Live ``CS_TPU_DAS`` switch (``utils/env_flags.switch``)."""
    return _env_flags.switch("CS_TPU_DAS")


def _engine_admitted(site) -> bool:
    return enabled() and not supervisor.probing() and supervisor.admit(site)


def _deferral_active() -> bool:
    """Whether an engine verify would defer its final pairing into the
    active assert-style batch scope instead of answering eagerly."""
    from consensus_specs_tpu.utils import bls as _bls
    return bool(_bls._batch_stack) and _bls.rlc_enabled()


# ---------------------------------------------------------------------------
# Batched verification dispatch
# ---------------------------------------------------------------------------

def _verify_engine(spec, row_commitments, row_ids, column_ids, cells,
                   proofs):
    from consensus_specs_tpu.das import kernels
    verdict = kernels.verify_cell_proof_batch(
        [bytes(c) for c in row_commitments],
        [int(r) for r in row_ids], [int(c) for c in column_ids],
        [bytes(c) for c in cells], [bytes(p) for p in proofs],
        spec.kzg_setup)
    if faults.corrupt_armed(SITE_VERIFY):
        verdict = not verdict
    return verdict


def dispatch_verify(spec, spec_body, row_commitments, row_ids, column_ids,
                    cells, proofs):
    """Engine-or-spec dispatch for ``verify_cell_proof_batch``."""
    site = SITE_VERIFY
    if _engine_admitted(site):
        fallback_exc = None
        try:
            faults.check(site)
            with supervisor.deadline_scope(site):
                verdict = _verify_engine(spec, row_commitments, row_ids,
                                         column_ids, cells, proofs)
        except (faults.InjectedFault, supervisor.DeadlineExceeded) as exc:
            fallback_exc = exc
        else:
            # inside an armed RLC scope the engine verdict is an
            # optimistic deferred True (the real pairing folds into the
            # block's flush) — there is no eager answer to audit against
            if supervisor.audit_due(site) and not _deferral_active():
                with supervisor.probe():
                    spec_verdict = spec_body(spec, row_commitments,
                                             row_ids, column_ids, cells,
                                             proofs)
                supervisor.audit_result(
                    site, bool(verdict) == bool(spec_verdict),
                    "batched cell-proof verdict diverged from the spec "
                    "loop")
                # the spec answer is authoritative on an audited call
                verdict = spec_verdict
            else:
                supervisor.note_success(site)
            _C_VERIFY["engine"].add()
            _C_CELLS["verified"].add(len(cells))
            return verdict
        faults.count_fallback(_C_FALLBACKS, fallback_exc, site=site)
    _C_VERIFY["spec"].add()
    return spec_body(spec, row_commitments, row_ids, column_ids, cells,
                     proofs)


# ---------------------------------------------------------------------------
# Recovery dispatch
# ---------------------------------------------------------------------------

def _recover_engine(spec, requests):
    from consensus_specs_tpu.das import kernels
    results = kernels.recover_cells_batch(requests, spec.kzg_setup)
    if faults.corrupt_armed(SITE_RECOVER) and results:
        # perturb the first recovered MISSING evaluation (received
        # evaluations are round-trip-asserted, so corrupt the part only
        # an audit can see); a request with nothing missing corrupts
        # position 0 instead — corrupt_armed has already booked the
        # corruption, so the result MUST really be wrong or the
        # sentinel-audit legs would flag a false silent corruption
        ids = {int(c) for c in requests[0][0]}
        fe = int(spec.FIELD_ELEMENTS_PER_CELL)
        pos = 0
        for cid in range(spec.cells_per_blob()):
            if cid not in ids:
                pos = cid * fe
                break
        row = list(results[0])
        row[pos] = (row[pos] + 1) % int(spec.BLS_MODULUS)
        results[0] = row
    return results


def dispatch_recover(spec, spec_body, cell_ids, cells_bytes):
    """Engine-or-spec dispatch for ``recover_polynomial``."""
    site = SITE_RECOVER
    if _engine_admitted(site):
        fallback_exc = None
        try:
            faults.check(site)
            with supervisor.deadline_scope(site):
                (result,) = _recover_engine(
                    spec, [(cell_ids, cells_bytes)])
        except (faults.InjectedFault, supervisor.DeadlineExceeded) as exc:
            fallback_exc = exc
        else:
            if supervisor.audit_due(site):
                with supervisor.probe():
                    spec_result = spec_body(spec, cell_ids, cells_bytes)
                supervisor.audit_result(
                    site, result == spec_result,
                    "batched recovery diverged from the spec loop")
                result = spec_result
            else:
                supervisor.note_success(site)
            _C_RECOVER["engine"].add()
            _C_CELLS["recovered"].add(len(cell_ids))
            return result
        faults.count_fallback(_C_FALLBACKS, fallback_exc, site=site)
    _C_RECOVER["spec"].add()
    return spec_body(spec, cell_ids, cells_bytes)


def recover_many(spec, requests):
    """Multi-blob recovery: the whole request list through ONE engine
    dispatch (shared vanishing-polynomial work across blobs missing the
    same columns), per-blob spec loop as the counted fallback.
    ``requests`` is ``[(cell_ids, cells_bytes), ...]``; returns each
    blob's full extended evaluations."""
    site = SITE_RECOVER
    spec_body = _spec_recover_body(spec)
    if _engine_admitted(site):
        fallback_exc = None
        try:
            faults.check(site)
            with supervisor.deadline_scope(site):
                results = _recover_engine(spec, requests)
        except (faults.InjectedFault, supervisor.DeadlineExceeded) as exc:
            fallback_exc = exc
        else:
            if supervisor.audit_due(site):
                with supervisor.probe():
                    spec_results = [spec_body(spec, ids, cbs)
                                    for ids, cbs in requests]
                supervisor.audit_result(
                    site, results == spec_results,
                    "batched multi-blob recovery diverged from the spec "
                    "loop")
                results = spec_results
            else:
                supervisor.note_success(site)
            _C_RECOVER["engine"].add()
            _C_CELLS["recovered"].add(sum(len(ids) for ids, _ in requests))
            return results
        faults.count_fallback(_C_FALLBACKS, fallback_exc, site=site)
    _C_RECOVER["spec"].add()
    return [spec_body(spec, ids, cbs) for ids, cbs in requests]


def _spec_recover_body(spec):
    """The UNWRAPPED markdown body of ``recover_polynomial`` on this
    spec's class (the wrapper stores it; fall back to the bound method
    for classes the installer never touched)."""
    fn = type(spec).__dict__.get("recover_polynomial")
    body = getattr(fn, "_das_spec_body", None)
    if body is not None:
        return body
    return lambda s, ids, cbs: s.recover_polynomial(ids, cbs)


# ---------------------------------------------------------------------------
# Installer
# ---------------------------------------------------------------------------

def install_das_accel(cls) -> None:
    """Wrap ``cls``'s own ``verify_cell_proof_batch`` and
    ``recover_polynomial`` with the engine dispatch.  Only methods
    defined on ``cls`` itself are wrapped (delta forks inherit the
    wrapped eip7594 surface); wrapping is idempotent.  Applied to every
    fork class of both ladders by ``forks.install_engines``."""
    fn = cls.__dict__.get("verify_cell_proof_batch")
    if fn is not None and not getattr(fn, "_das_wrapper", False):
        @functools.wraps(fn)
        def verify_cell_proof_batch(self, row_commitments, row_ids,
                                    column_ids, cells, proofs, _orig=fn):
            return dispatch_verify(self, _orig, row_commitments, row_ids,
                                   column_ids, cells, proofs)
        verify_cell_proof_batch._das_wrapper = True
        verify_cell_proof_batch._das_spec_body = fn
        setattr(cls, "verify_cell_proof_batch", verify_cell_proof_batch)

    fn = cls.__dict__.get("recover_polynomial")
    if fn is not None and not getattr(fn, "_das_wrapper", False):
        @functools.wraps(fn)
        def recover_polynomial(self, cell_ids, cells_bytes, _orig=fn):
            return dispatch_recover(self, _orig, cell_ids, cells_bytes)
        recover_polynomial._das_wrapper = True
        recover_polynomial._das_spec_body = fn
        setattr(cls, "recover_polynomial", recover_polynomial)
