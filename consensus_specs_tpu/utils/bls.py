"""Pluggable BLS backend switch.

Mirrors the reference's backend-switch design
(``tests/core/pyspec/eth2spec/utils/bls.py:30-104``): one module-level
``bls`` API whose implementation is swapped at runtime —

  use_py()       pure-Python oracle (role of the reference's py_ecc)
  use_jax()      batched JAX kernels, jit-compiled for TPU (replaces the
                 reference's milagro/arkworks Rust backends)
  use_fastest()  jax on an accelerator, else native C, else py

plus the test kill-switch ``bls_active`` with STUB constants
(``bls.py:49-57,93-104``): when inactive, Sign returns a stub and verifies
trivially pass — used by the harness's @never_bls/@always_bls decorators.
"""
from contextlib import contextmanager
from typing import Sequence

from consensus_specs_tpu import faults as _faults
from consensus_specs_tpu import supervisor
from consensus_specs_tpu.obs import registry as _obs_registry
from consensus_specs_tpu.obs import tracing as _tracing
from consensus_specs_tpu.utils import env_flags as _env_flags
from consensus_specs_tpu.utils.lru import LRUDict
from consensus_specs_tpu.ops.bls12_381 import ciphersuite as _py_backend
from consensus_specs_tpu.ops.bls12_381.fields import R_ORDER as CURVE_ORDER  # noqa: F401
from consensus_specs_tpu.ops.bls12_381.curve import (  # noqa: F401
    G1Point, G2Point, G1_GENERATOR, G2_GENERATOR,
    g1_from_compressed as bytes48_to_G1,
    g2_from_compressed as bytes96_to_G2,
)
from consensus_specs_tpu.ops.bls12_381.pairing import multi_pairing_check as pairing_check  # noqa: F401 (spec API)

bls_active = True

STUB_SIGNATURE = b"\x11" * 96
STUB_PUBKEY = b"\x22" * 48
# stub return of signature_to_G2 when bls is inactive: the G2 infinity point
STUB_COORDINATES = G2Point.inf()

_backend = _py_backend
_backend_name = "py"


def use_py():
    global _backend, _backend_name
    if _backend_name != "py":
        # a differential run must exercise the newly selected backend,
        # so memoized results from the other one are dropped; repeated
        # use_py() calls (the harness resets the backend per test) keep
        # the memo — cross-test reuse is its whole payoff
        clear_verify_memo()
    _backend = _py_backend
    _backend_name = "py"


def use_jax():
    global _backend, _backend_name
    from consensus_specs_tpu.ops import bls_jax
    if _backend_name != "jax":
        clear_verify_memo()
    _backend = bls_jax
    _backend_name = "jax"


def use_native():
    """CPU-native C backend (csrc/bls12_381.c) — the role the reference's
    Rust milagro/arkworks bindings play (bls.py:61-72); ~20-25x the
    python oracle on one core."""
    global _backend, _backend_name
    from consensus_specs_tpu.ops import native_bls
    if not native_bls.available():
        raise RuntimeError("native BLS library unavailable")
    if _backend_name != "native":
        clear_verify_memo()
    _backend = native_bls
    _backend_name = "native"


def use_fastest():
    """Backend ladder (reference ``fastest_bls``, bls.py:35-53): the JAX
    kernels when JAX's default backend is an accelerator, else the
    native C library, else the python oracle.  On a bare CPU the jax
    path pays minutes of XLA compile for sub-oracle throughput, so it is
    only 'fastest' when a real device is present.

    Asks JAX itself (initializing the backend if nothing has yet): the
    platform is whatever JAX says, and ``JAX_PLATFORMS=cpu`` is how a
    caller asks for the host."""
    import jax
    if jax.default_backend() != "cpu":
        use_jax()
        return
    try:
        use_native()
    except RuntimeError:
        use_py()


def backend_name() -> str:
    return _backend_name


# ---------------------------------------------------------------------------
# Deferred batch verification — the TPU-native block path.
#
# The reference verifies a block's signatures one FFI call at a time inside
# the serial ``for_ops`` loop (``specs/phase0/beacon-chain.md:1757-1774``).
# Here ``process_block`` opens a batch context; every assert-style
# ``Verify``/``FastAggregateVerify`` inside it enqueues its (pubkeys, msg,
# sig) triple and optimistically returns True, and the block flushes the
# whole batch as ONE device dispatch.  Any invalid signature then raises
# AssertionError, which keeps exception-as-invalidity semantics: a block
# is atomically valid or invalid, and partially-mutated state is discarded
# by every caller on failure (reference ``test/context.py:299-310``,
# ``fork-choice.md`` on_block state copy).
#
# Only *assert-style* verifications may be deferred.  Conditional ones
# (deposit proofs of possession, where the boolean steers state) must use
# the eager paths below.
#
# Flush strategy (``CS_TPU_BLS_RLC``, default on): the whole queue folds
# into a random-linear-combination product — 2 MSMs + ONE pairing check
# for the block (``ops/bls_rlc.py``; math and soundness documented
# there).  On a combined failure (or a structurally invalid item) the
# flush re-runs the per-lane path to bisect and report exactly which
# item failed, so assert semantics are unchanged.  ``bls.flush{path=
# rlc|lanes|fallback}`` counts which strategy answered; ``bls.pairings``
# counts pairing-check evaluations so "one pairing per block" is
# counter-assertable.  The fallback series carries a ``reason`` label:
# ``bisect`` for the organic combined-failure re-run, ``injected`` for
# harness-scheduled faults (``consensus_specs_tpu/faults.py``).
# ---------------------------------------------------------------------------

_FLUSH_RLC = _obs_registry.counter("bls.flush").labels(path="rlc")
_FLUSH_LANES = _obs_registry.counter("bls.flush").labels(path="lanes")
_FLUSH_FALLBACK = {
    "bisect": _obs_registry.counter(
        "bls.flush").labels(path="fallback", reason="bisect"),
    "injected": _obs_registry.counter(
        "bls.flush").labels(path="fallback", reason="injected"),
    "deadline": _obs_registry.counter(
        "bls.flush").labels(path="fallback", reason="deadline"),
}
_PAIRINGS = _obs_registry.counter("bls.pairings").labels()


def rlc_enabled() -> bool:
    """RLC flush switch: live env re-read when the variable is present
    (CI legs flip it after import), else the import-time snapshot —
    the shared ``env_flags.switch`` contract."""
    return _env_flags.switch("CS_TPU_BLS_RLC")


class DeferredBatch:
    """Signature-verification triples (and deferred raw pairing-product
    checks, e.g. the blob-KZG batch) collected under one block."""

    def __init__(self):
        self.items = []            # (pubkeys, message, signature)
        self.item_keys = []        # per item: memo keys to record at flush
        self.pairing_checks = []   # (pairs, label): raw product checks
        self._seen = {}            # triple -> index (in-batch dedup)
        self.last_results = None
        self.last_pairing_results = None

    def add(self, pubkeys, message, signature, memo_key=None):
        item = ([bytes(pk) for pk in pubkeys],
                bytes(message), bytes(signature))
        if memo_key is None:
            memo_key = ("fav", tuple(item[0]), item[1], item[2])
        dedup = (tuple(item[0]), item[1], item[2])
        idx = self._seen.get(dedup)
        if idx is not None:
            # identical triple already queued this block: one device lane
            # serves both call sites, both memo keys get the result
            if memo_key not in self.item_keys[idx]:
                self.item_keys[idx].append(memo_key)
            return
        self._seen[dedup] = len(self.items)
        self.items.append(item)
        self.item_keys.append([memo_key])

    def add_pairing_check(self, pairs, label=""):
        """Defer a raw product-pairing check ``prod e(P_i, Q_i) == 1``
        (oracle point pairs).  Folds into the RLC flush with its own
        random coefficient; evaluated individually on the bisect path."""
        self.pairing_checks.append(
            ([(p, q) for p, q in pairs], str(label)))

    @staticmethod
    def _lane_results(items) -> list:
        if not items:
            return []
        if _backend_name == "jax":
            from consensus_specs_tpu.ops import bls_jax
            return bls_jax.verify_aggregates_batch(items)
        return [_backend.FastAggregateVerify(pks, msg, sig)
                for pks, msg, sig in items]

    @staticmethod
    def _eval_pairing_check(pairs) -> bool:
        from consensus_specs_tpu.ops.kzg import _pairing_check
        return _pairing_check(pairs)

    def flush(self) -> bool:
        with _tracing.span("bls.batch.flush"):
            return self._flush()

    def _flush(self) -> bool:
        items, keys = self.items, self.item_keys
        checks = self.pairing_checks
        self.items, self.item_keys, self.pairing_checks = [], [], []
        self._seen = {}
        if not items and not checks:
            return True
        site = "bls.flush"
        verdict = None
        audited = False
        if rlc_enabled() and supervisor.admit(site):
            fallback_exc = None
            try:
                _faults.check(site)
                with supervisor.deadline_scope(site):
                    from consensus_specs_tpu.ops import bls_rlc
                    verdict = bls_rlc.combined_check(items, checks,
                                                     _backend_name)
            except (_faults.InjectedFault,
                    supervisor.DeadlineExceeded) as exc:
                # the RLC combine "failed": degrade to the per-lane
                # path, exactly like a combined-verdict failure
                fallback_exc = exc
            else:
                if verdict is not None:
                    _PAIRINGS.add()      # the one combined product pairing
                    if _faults.corrupt_armed(site):
                        # silent-corruption injection (sentinel-audit
                        # test vector): the combined check lies in
                        # whichever direction the true verdict isn't
                        verdict = not verdict
                    audited = supervisor.audit_due(site)
                if verdict is True and not audited:
                    _FLUSH_RLC.add()
                    supervisor.note_success(site)
                    for ks in keys:
                        for k in ks:
                            _memo_put(k, True)
                    self.last_results = [True] * len(items)
                    self.last_pairing_results = [True] * len(checks)
                    return True
            # combined failure (False), structurally invalid item
            # (None), or an injected/deadline fault: bisect through the
            # per-lane path for exact per-item reporting.  Only an
            # audited verdict=True flush skips the count — there the
            # lanes run purely as the sentinel's cross-check, not as a
            # fallback; an audited combined FAILURE is still the
            # organic bisect and must book (and feed the breaker) like
            # any other
            if not audited or verdict is not True:
                _faults.count_fallback(_FLUSH_FALLBACK, fallback_exc,
                                       organic="bisect", site=site)
        else:
            _FLUSH_LANES.add()
        results = self._lane_results(items)
        _PAIRINGS.add(len(items))
        pairing_results = [self._eval_pairing_check(pairs)
                           for pairs, _ in checks]
        _PAIRINGS.add(len(checks))
        if audited:
            lanes_ok = all(bool(r) for r in results) \
                and all(pairing_results)
            ok = (verdict is True) == lanes_ok
            supervisor.audit_result(
                site, ok, "RLC combined verdict diverged from the "
                "per-lane pairing checks")
            if ok and verdict is True:
                _FLUSH_RLC.add()
        for ks, ok in zip(keys, results):
            for k in ks:
                _memo_put(k, bool(ok))
        self.last_results = [bool(r) for r in results]
        self.last_pairing_results = pairing_results
        return all(results) and all(pairing_results)

    def assert_valid(self):
        if not self.flush():
            failed = [i for i, r in enumerate(self.last_results or [])
                      if not r]
            failed_checks = [i for i, r in
                             enumerate(self.last_pairing_results or [])
                             if not r]
            raise AssertionError(
                "batched signature verification failed "
                f"(items {failed}, deferred checks {failed_checks})")


_batch_stack = []


@contextmanager
def batched_verification():
    """Defer assert-style signature checks to one batched dispatch.

    Re-entrant: a nested context joins the enclosing batch so a whole
    ``state_transition`` (block signature + block body) flushes once.
    """
    if _batch_stack:
        yield _batch_stack[-1]
        return
    batch = DeferredBatch()
    _batch_stack.append(batch)
    try:
        yield batch
    finally:
        _batch_stack.pop()


def batch_scope_active() -> bool:
    """True while any deferred-verification scope is open — callers that
    want to INSTALL an outermost scope (the gen runner's per-case fold)
    probe this instead of racing :func:`scoped_batch`'s RuntimeError."""
    return bool(_batch_stack)


@contextmanager
def scoped_batch(batch):
    """Install ``batch`` as the outermost deferred-verification scope.

    The serving pipeline (``consensus_specs_tpu/serving``) uses this to
    interpose a window-spanning :class:`DeferredBatch` subclass: every
    nested :func:`batched_verification` context (one per ``on_block``)
    then joins the window batch, so signature triples from several
    in-flight blocks dedup (equivocating siblings share device lanes)
    and fold into ONE flush at the window barrier.  Refuses to nest
    inside an active scope — interposition means owning the outermost
    scope, and silently joining someone else's batch would defer their
    asserts past the point they resolve them."""
    if _batch_stack:
        raise RuntimeError(
            "bls.scoped_batch: a batch scope is already active")
    _batch_stack.append(batch)
    try:
        yield batch
    finally:
        popped = _batch_stack.pop()
        assert popped is batch


def defer_pairing_check(pairs, label="") -> bool:
    """Queue a raw product-pairing check ``prod e(P_i, Q_i) == 1`` (oracle
    point pairs) into the active batch context, to fold into the block's
    single RLC pairing.  Returns False when no batch context is active or
    the RLC path is off — the caller must then evaluate eagerly.

    Deferred checks are assert-style by contract (the batched-
    verification scope rule above): the optimistic True is only sound
    when the caller asserts the result and block-level failure discards
    the state.
    """
    if not _batch_stack or not rlc_enabled():
        return False
    _batch_stack[-1].add_pairing_check(pairs, label)
    return True


def only_with_bls(alt_return=None):
    """Decorator: skip the wrapped check when bls is disabled."""
    def decorator(fn):
        def wrapper(*args, **kwargs):
            if not bls_active:
                return alt_return
            return fn(*args, **kwargs)
        return wrapper
    return decorator


# Verification results are pure functions of their byte inputs, so a
# bounded memo is semantically transparent. It pays off because the
# harness reuses one cached genesis per (fork, preset): identical
# (pubkey, signing-root, signature) triples recur across tests — every
# repeat verification of a proposer/randao/attestation signature becomes
# a dict hit instead of a multi-second pure-python pairing. The memo is
# cleared on every backend switch so a differential run (py vs jax over
# the same inputs) always exercises the newly selected backend, and
# benchmarks can call ``clear_verify_memo`` between reps so they time
# pairings, not dict hits.
_verify_memo = LRUDict(1 << 16, name="bls_verify")


def clear_verify_memo() -> None:
    _verify_memo.clear()


def _memo_get(key):
    return _verify_memo.get(key)


def _memo_put(key, value: bool) -> bool:
    _verify_memo[key] = value
    return value


@only_with_bls(alt_return=True)
def Verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    key = ("v", bytes(pk), bytes(msg), bytes(sig))
    if _batch_stack:
        # memo before enqueue: a repeated signature (replayed block)
        # skips the device lane entirely; a memoized failure surfaces
        # immediately (assert-style callers raise just as they would at
        # flush).  Results memo back in at flush.
        hit = _memo_get(key)
        if hit is None:
            _batch_stack[-1].add([pk], msg, sig, memo_key=key)
            return True
        return hit
    hit = _memo_get(key)
    if hit is not None:
        return hit
    return _memo_put(key, _backend.Verify(bytes(pk), bytes(msg), bytes(sig)))


@only_with_bls(alt_return=True)
def VerifyEager(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """Immediate verification even inside a batch context — for call sites
    where the boolean result steers state (deposit proof of possession,
    ``specs/phase0/beacon-chain.md:1877``) rather than block validity."""
    key = ("v", bytes(pk), bytes(msg), bytes(sig))
    hit = _memo_get(key)
    if hit is not None:
        return hit
    return _memo_put(key, _backend.Verify(bytes(pk), bytes(msg), bytes(sig)))


@only_with_bls(alt_return=True)
def AggregateVerify(pks: Sequence[bytes], msgs: Sequence[bytes], sig: bytes) -> bool:
    key = ("av", tuple(bytes(p) for p in pks),
           tuple(bytes(m) for m in msgs), bytes(sig))
    hit = _memo_get(key)
    if hit is not None:
        return hit
    return _memo_put(key, _backend.AggregateVerify(
        [bytes(p) for p in pks], [bytes(m) for m in msgs], bytes(sig)))


@only_with_bls(alt_return=True)
def FastAggregateVerify(pks: Sequence[bytes], msg: bytes, sig: bytes) -> bool:
    key = ("fav", tuple(bytes(p) for p in pks), bytes(msg), bytes(sig))
    if _batch_stack:
        # memo before enqueue (see Verify): repeats skip device work
        hit = _memo_get(key)
        if hit is None:
            _batch_stack[-1].add(pks, msg, sig, memo_key=key)
            return True
        return hit
    hit = _memo_get(key)
    if hit is not None:
        return hit
    return _memo_put(key, _backend.FastAggregateVerify(
        [bytes(p) for p in pks], bytes(msg), bytes(sig)))


@only_with_bls(alt_return=STUB_SIGNATURE)
def Aggregate(signatures: Sequence[bytes]) -> bytes:
    return _backend.Aggregate([bytes(s) for s in signatures])


@only_with_bls(alt_return=STUB_SIGNATURE)
def Sign(sk: int, msg: bytes) -> bytes:
    return _backend.Sign(sk, bytes(msg))


@only_with_bls(alt_return=STUB_PUBKEY)
def AggregatePKs(pubkeys: Sequence[bytes]) -> bytes:
    return _backend.AggregatePKs([bytes(p) for p in pubkeys])


@only_with_bls(alt_return=STUB_PUBKEY)
def SkToPk(sk: int) -> bytes:
    # NOTE: deliberate divergence — the reference stubs SkToPk with the
    # 96-byte STUB_SIGNATURE (bls.py:182-183), which is the wrong width for a
    # pubkey; we return the 48-byte STUB_PUBKEY instead.
    return _backend.SkToPk(sk)


@only_with_bls(alt_return=True)
def KeyValidate(pk: bytes) -> bool:
    return _backend.KeyValidate(bytes(pk))


@only_with_bls(alt_return=STUB_COORDINATES)
def signature_to_G2(sig: bytes) -> G2Point:
    return bytes96_to_G2(bytes(sig))


# ---------------------------------------------------------------------------
# Raw point helpers (reference bls.py:190-326) — used directly by the KZG
# spec functions (g1_lincomb, pairing checks) and by test vector generators.
# ---------------------------------------------------------------------------

def add(lhs, rhs):
    return lhs + rhs


def multiply(point, scalar: int):
    return point.mult(int(scalar))


def neg(point):
    return -point


def Z1():
    return G1Point.inf()


def Z2():
    return G2Point.inf()


def G1():
    return G1_GENERATOR


def G2():
    return G2_GENERATOR


def G1_to_bytes48(point) -> bytes:
    return point.to_compressed()


def G2_to_bytes96(point) -> bytes:
    return point.to_compressed()
