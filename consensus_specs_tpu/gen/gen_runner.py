"""Vector-generation runner.

Reference: ``gen_base/gen_runner.py`` — CLI, skip-if-complete resume,
INCOMPLETE tags, error log, diagnostics JSON, YAML + ssz-snappy part
writers.  Output tree:
``tests/<preset>/<fork>/<runner>/<handler>/<suite>/<case>/<part>``.

The case loop is shared with the corpus factory
(``consensus_specs_tpu/gen/corpus.py``): :func:`collect_cases` turns
providers into a filtered case list, :func:`run_cases` executes it
serially or over a fork-start worker pool, and
:func:`write_run_reports` merges diagnostics/error logs under an
exclusive file lock so concurrent generator processes (``make -j``
today, the orchestrator's pool tomorrow) stop losing each other's
read-modify-write updates.
"""
import argparse
import fcntl
import json
import os
import shutil
import sys
import time
import traceback

import yaml

from consensus_specs_tpu.obs import registry as _obs_registry
from consensus_specs_tpu.recovery.atomic import (
    atomic_replace_bytes, atomic_write_bytes, atomic_write_json)
from consensus_specs_tpu.utils import bls
from consensus_specs_tpu.utils import snappy
from consensus_specs_tpu.utils.ssz.types import SSZValue
from consensus_specs_tpu.debug.encode import encode

TIME_THRESHOLD_TO_PRINT = 1.0  # seconds (reference gen_base/settings.py)

# What a failing *case* is allowed to raise: the spec's
# exception-as-invalidity surface (AssertionError and the container/
# math errors it degrades to), case-parameter mistakes, and part-file
# I/O.  Deliberately NOT `Exception`: a NameError/TypeError in spec or
# infra code — or an InjectedFault (a BaseException) from
# ``consensus_specs_tpu/faults`` — is a bug to surface, not a case to
# skip past.
_CASE_FAILURES = (AssertionError, IndexError, KeyError, ValueError,
                  ArithmeticError, OSError)

_CASE_REPLAYS = _obs_registry.counter("gen.case_replays").labels()
_CASE_FOLDED = _obs_registry.counter("gen.case_batches").labels(path="folded")
_SLOW_CASES = _obs_registry.counter("gen.slow_cases").labels()


def _write_yaml(path: str, data) -> None:
    # every emitted vector file lands by atomic rename
    # (recovery/atomic.py; speclint R901): the corpus is consumed by
    # OTHER clients — a torn part file would fail their decoders with
    # no hint the generator died mid-write.  Rename-only (no per-file
    # fsync): a crashed case directory is distrusted wholesale by the
    # INCOMPLETE tag below, so per-part durability buys nothing at
    # thousands of files per corpus run
    atomic_replace_bytes(path, yaml.safe_dump(
        data, default_flow_style=None, sort_keys=False).encode("utf-8"))


def _write_part_bytes(path: str, data: bytes) -> None:
    atomic_replace_bytes(path, data)


def _encode_meta(value):
    if isinstance(value, SSZValue):
        return encode(value)
    if isinstance(value, bytes):
        return "0x" + value.hex()
    if isinstance(value, dict):
        return {k: _encode_meta(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_encode_meta(v) for v in value]
    return value


class RawSSZBytes(bytes):
    """Part wrapper: pre-serialized (possibly deliberately malformed) SSZ
    bytes to be written as <name>.ssz_snappy — the ``ssz_generic``
    invalid-encoding cases need byte streams no typed value can produce."""


class YamlPart(dict):
    """Part wrapper: force a <name>.yaml file even for scalar payloads."""


def write_part(case_dir: str, name: str, value, meta: dict) -> None:
    """One yielded (name, value) part -> file(s) (reference
    gen_runner.py:399-426 output kinds)."""
    if value is None:
        return  # absent part (e.g. no post state for invalid cases)
    if isinstance(value, RawSSZBytes):
        _write_part_bytes(os.path.join(case_dir, f"{name}.ssz_snappy"),
                          snappy.compress(bytes(value)))
    elif isinstance(value, YamlPart):
        payload = value["value"] if set(value) == {"value"} else dict(value)
        _write_yaml(os.path.join(case_dir, f"{name}.yaml"),
                    _encode_meta(payload))
    elif isinstance(value, SSZValue):
        _write_part_bytes(os.path.join(case_dir, f"{name}.ssz_snappy"),
                          snappy.compress(value.serialize()))
    elif isinstance(value, (list, tuple)) and value \
            and all(isinstance(v, SSZValue) for v in value):
        for i, v in enumerate(value):
            _write_part_bytes(
                os.path.join(case_dir, f"{name}_{i}.ssz_snappy"),
                snappy.compress(v.serialize()))
        meta[f"{name}_count"] = len(value)
    elif isinstance(value, (dict, list, tuple)):
        _write_yaml(os.path.join(case_dir, f"{name}.yaml"),
                    _encode_meta(value))
    else:
        meta[name] = _encode_meta(value)


class _CaseBatch(bls.DeferredBatch):
    """A deferred batch that stays queued across the per-block
    ``assert_valid()`` calls inside a test case: while ``_deferring``
    is set, ``flush()`` reports optimistic success without draining,
    so every signature check of the case (randao reveals, proposer
    signatures, attestation aggregates across a whole ``next_epoch``
    of blocks) folds into the ONE real RLC pairing issued by
    :meth:`resolve` when the case completes — the serving pipeline's
    window-batch trick (``serving/pipeline.py``) applied per case."""

    _deferring = True

    def flush(self):
        if self._deferring:
            return True
        return super().flush()

    def resolve(self) -> bool:
        """The case's single real flush (one combined pairing)."""
        self._deferring = False
        return bls.DeferredBatch.flush(self)


def _run_case(test_case, case_dir: str, log, batch=None) -> str:
    """Execute one case and (on success) write its part files.

    Returns ``'generated'`` / ``'skipped'`` / ``'error'`` — or
    ``'replay'`` when running under a folded case ``batch`` and the
    case either raised or the batch's combined verification failed:
    the caller then discards everything and re-runs the case on the
    plain per-block path, which is authoritative.  Nothing is written
    and nothing is booked for a ``'replay'`` outcome."""
    from consensus_specs_tpu.test_infra import context as ctx

    incomplete_tag = os.path.join(case_dir, "INCOMPLETE")
    meta = {}
    parts = []

    def collector(part):
        # snapshot NOW: the test keeps mutating the state object it just
        # yielded (the 'pre' part must not turn into the post state)
        name, value = part
        if isinstance(value, SSZValue):
            value = value.copy()
        elif isinstance(value, (list, tuple)):
            value = [v.copy() if isinstance(v, SSZValue) else v
                     for v in value]
        parts.append((name, value))

    old_collector = ctx.VECTOR_COLLECTOR
    old_fork, old_preset = ctx.ONLY_FORK, ctx.DEFAULT_TEST_PRESET
    ctx.VECTOR_COLLECTOR = collector
    ctx.ONLY_FORK = test_case.exec_fork
    ctx.DEFAULT_TEST_PRESET = test_case.preset_name
    try:
        try:
            if batch is not None:
                with bls.scoped_batch(batch):
                    result = test_case.case_fn()
                    _drain(result, collector)
            else:
                result = test_case.case_fn()
                _drain(result, collector)
        except BaseException as exc:  # noqa: B036 — pytest.skip raises
            # a test skipping itself (preset/fork gating) is not an error
            if type(exc).__name__ in ("Skipped", "OutcomeException"):
                shutil.rmtree(case_dir)
                return "skipped"
            raise
        if batch is not None and not batch.resolve():
            # the case's combined signature fold found an invalid item
            # (an expected-invalid signature whose assertion the
            # optimistic scope deferred past its resolution point):
            # the optimistic run's parts are untrustworthy — discard
            # them and let the caller replay on the plain path
            return "replay"
        bls_mode = getattr(test_case.case_fn, "_bls_mode", None)
        if bls_mode == "always":
            meta["bls_setting"] = 1
        elif bls_mode == "never":
            meta["bls_setting"] = 2
        for name, value in parts:
            write_part(case_dir, name, value, meta)
        if meta:
            _write_yaml(os.path.join(case_dir, "meta.yaml"),
                        _encode_meta(meta))
        os.remove(incomplete_tag)
        return "generated"
    except SystemExit:
        # a test guarding an expected-rejection path with SystemExit
        # ("this invalid input must NOT be accepted"): under the folded
        # scope the acceptance IS the deferral artifact — the scope
        # optimistically answered True for a signature the plain path
        # rejects — so the authoritative replay decides.  Outside a
        # fold it is a real abort and must escape.
        if batch is not None:
            return "replay"
        raise
    except _CASE_FAILURES as exc:
        # the expected per-case failure surface: spec invalidity
        # assertions (exception-as-invalidity), bad case parameters,
        # and part-file I/O.  Anything else — including an injected
        # fault from the adversarial harness, which subclasses
        # BaseException precisely so no catch-all can eat it — must
        # escape and kill the run loudly.  Every swallowed failure is
        # accounted on the obs registry so a fault-injection or
        # flakiness sweep sees generator losses instead of a silently
        # thinner corpus.
        if batch is not None:
            # under the folded scope an exception may be an artifact of
            # deferred verification (an expect-assertion-error case
            # whose assert was optimistically deferred): the plain
            # replay is authoritative for both the outcome and the
            # error accounting
            return "replay"
        _obs_registry.counter("gen.case_errors").labels(
            error=type(exc).__name__).add()
        log.append({"case": test_case.dir_path(),
                    "error": traceback.format_exc()})
        return "error"
    finally:
        ctx.VECTOR_COLLECTOR = old_collector
        ctx.ONLY_FORK, ctx.DEFAULT_TEST_PRESET = old_fork, old_preset


def _drain(result, collector) -> None:
    # decorated spec tests consume their own yields (forwarding
    # through ctx.VECTOR_COLLECTOR); a direct-provider case fn is
    # a bare generator whose parts must be drained here
    import inspect
    if inspect.isgenerator(result):
        for part in result:
            if part is not None:
                collector(part)


def generate_test_vector(test_case, output_dir: str, log, fold=False):
    """Run one case and materialize its part files (reference
    gen_runner.py:304-361).  Returns ``(status, elapsed_seconds)``
    with status 'generated'/'skipped'/'error'.

    With ``fold=True`` (and a batchable, RLC-eligible case) the case
    first runs under a :class:`_CaseBatch`: every assert-style
    signature check defers into one combined pairing resolved when the
    case completes.  If that optimistic run fails in ANY way — the
    combined check finds an invalid signature, or the case raises —
    the whole attempt is discarded and the case replays on the plain
    per-block path (counted ``gen.case_replays``), so emitted vectors
    are byte-identical to a fold-free run by construction.
    """
    case_dir = os.path.join(output_dir, test_case.dir_path())
    incomplete_tag = os.path.join(case_dir, "INCOMPLETE")

    if os.path.exists(case_dir) and not os.path.exists(incomplete_tag):
        return "skipped", 0.0
    if os.path.exists(case_dir):
        shutil.rmtree(case_dir)
    os.makedirs(case_dir, exist_ok=True)
    atomic_write_bytes(incomplete_tag, b"INCOMPLETE")

    start = time.time()
    if fold and getattr(test_case, "batchable", False) \
            and bls.rlc_enabled() and not bls.batch_scope_active():
        status = _run_case(test_case, case_dir, log, batch=_CaseBatch())
        if status != "replay":
            if status == "generated":
                _CASE_FOLDED.add()
            return status, time.time() - start
        _CASE_REPLAYS.add()
        # the discarded attempt may have left part files; reset the
        # case directory so the replay writes a clean slate
        shutil.rmtree(case_dir)
        os.makedirs(case_dir, exist_ok=True)
        atomic_write_bytes(incomplete_tag, b"INCOMPLETE")
    status = _run_case(test_case, case_dir, log)
    return status, time.time() - start


# Module-global case table for the fork-based worker pool: closures are
# not picklable, but with the 'fork' start method child processes inherit
# the parent image, so workers receive INDICES into this list instead of
# the cases themselves (the role of the reference's pathos/dill pool,
# gen_base/gen_runner.py:259-264, without the dill dependency).
_POOL_CASES = []
_POOL_OUTPUT_DIR = None
_POOL_FOLD = False


def _pool_worker(idx: int):
    """One case in a forked child.  Counters a case bumps
    (``gen.case_errors``, ``bls.pairings``, cache hit/miss series, …)
    are booked in the CHILD's registry, which dies with the child — so
    the per-case counter deltas ride back through the pool result and
    the parent re-books them (``obs.registry.book_flat_deltas``)."""
    from consensus_specs_tpu.test_infra.metrics import counting
    log = []
    with counting() as delta:
        result, elapsed = generate_test_vector(
            _POOL_CASES[idx], _POOL_OUTPUT_DIR, log, fold=_POOL_FOLD)
    return idx, result, elapsed, log, delta.nonzero()


def _fork_safe() -> bool:
    """Forking after XLA backends initialize is deadlock-prone (the
    child inherits live client threads/mutexes).  Generators run the
    pure-python BLS backend and never dispatch to a device, so the
    backends are normally untouched — but if anything DID initialize
    them, degrade to serial instead of risking a silent hang."""
    try:
        from jax._src import xla_bridge as xb
        return not xb.backends_are_initialized()
    except (ImportError, AttributeError) as exc:
        # jax absent, or the private probe moved between versions:
        # forking is then safe by definition (no backend could have
        # initialized), but account the degraded probe so a
        # version bump that breaks it is visible in obs_report
        _obs_registry.counter("gen.fork_probe_misses").labels(
            error=type(exc).__name__).add()
        return True


def _note_slow(test_case, elapsed: float) -> None:
    """Slow-case reporting, always from the PARENT process: forked
    children used to print interleaved raw lines mid-run; now the pool
    result carries the timing and the parent prints coherently."""
    if elapsed > TIME_THRESHOLD_TO_PRINT:
        _SLOW_CASES.add()
        print(f"  {test_case.dir_path()}: {elapsed:.1f}s")


def collect_cases(providers, preset_list=None, fork_list=None,
                  force=False, output_dir=None, collect_only=False):
    """Provider loop -> filtered case list (reference
    gen_runner.py:230-258).  ``force`` removes pre-existing complete
    case directories so the run regenerates them."""
    cases = []
    collected = 0
    for provider in providers:
        provider.prepare()
        for test_case in provider.make_cases():
            if preset_list is not None \
                    and test_case.preset_name not in preset_list:
                continue
            if fork_list is not None \
                    and test_case.fork_name not in fork_list:
                continue
            collected += 1
            if collect_only:
                print(test_case.dir_path())
                continue
            if force:
                case_dir = os.path.join(output_dir, test_case.dir_path())
                if os.path.exists(case_dir):
                    shutil.rmtree(case_dir)
            cases.append(test_case)
    return cases, collected


def run_cases(cases, output_dir: str, workers=1, fold=False):
    """Execute ``cases`` serially or over a fork-start pool.

    Returns ``(outcomes, error_log)`` where outcomes is a list of
    ``(case, status, elapsed)``.  Pool workers return their counter
    deltas, which are booked into THIS process's registry, and their
    slow-case reports, which print here instead of interleaving."""
    error_log = []
    outcomes = []
    import multiprocessing
    if workers > 1 and len(cases) > 1 \
            and "fork" in multiprocessing.get_all_start_methods() \
            and _fork_safe():
        global _POOL_CASES, _POOL_OUTPUT_DIR, _POOL_FOLD
        _POOL_CASES, _POOL_OUTPUT_DIR, _POOL_FOLD = \
            cases, output_dir, fold
        mp = multiprocessing.get_context("fork")
        try:
            with mp.Pool(min(workers, len(cases))) as pool:
                for idx, result, elapsed, log, deltas in \
                        pool.imap_unordered(_pool_worker, range(len(cases))):
                    _obs_registry.book_flat_deltas(deltas)
                    outcomes.append((cases[idx], result, elapsed))
                    error_log.extend(log)
                    _note_slow(cases[idx], elapsed)
        finally:
            _POOL_CASES, _POOL_OUTPUT_DIR, _POOL_FOLD = [], None, False
    else:
        for test_case in cases:
            result, elapsed = generate_test_vector(
                test_case, output_dir, error_log, fold=fold)
            outcomes.append((test_case, result, elapsed))
            _note_slow(test_case, elapsed)
    return outcomes, error_log


# ---------------------------------------------------------------------------
# run reports: diagnostics + error log, lost-update-safe
# ---------------------------------------------------------------------------
# Both files are read-modify-write merges shared by EVERY generator
# process targeting one output tree.  Concurrent generators (make -j,
# the corpus orchestrator's subprocess smoke legs) used to silently
# drop each other's entries; an exclusive flock around the
# read+mutate+rename sequence makes the merge atomic.  The lock file
# lives beside the target (``<name>.lock``) so locking never touches
# the file the readers trust.

def _locked_merge_json(path: str, mutate) -> None:
    with open(path + ".lock", "a") as lock_f:
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            payload = {}
            if os.path.exists(path):
                with open(path) as f:
                    payload = json.load(f)
            mutate(payload)
            atomic_write_json(path, payload)
        finally:
            fcntl.flock(lock_f, fcntl.LOCK_UN)


def _locked_append_text(path: str, text: str) -> None:
    with open(path + ".lock", "a") as lock_f:
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            existing = ""
            if os.path.exists(path):
                with open(path) as f:
                    existing = f.read()
            atomic_write_bytes(path, (existing + text).encode("utf-8"))
        finally:
            fcntl.flock(lock_f, fcntl.LOCK_UN)


def write_run_reports(generator_name: str, output_dir: str,
                      diagnostics: dict, error_log, timings=None) -> None:
    """Merge one generator's diagnostics (and per-case ``timings``, the
    corpus scheduler's cost profile) + error log into the output tree."""
    os.makedirs(output_dir, exist_ok=True)
    if error_log:
        log_path = os.path.join(
            output_dir, f"testgen_error_log_{generator_name}.txt")
        _locked_append_text(log_path, "".join(
            f"{entry['case']}\n{entry['error']}\n" for entry in error_log))
    diag_path = os.path.join(output_dir, "diagnostics_obj.json")

    def _merge(existing):
        entry = {k: v for k, v in diagnostics.items()
                 if k != "test_identifiers"}
        if timings:
            # keep the profile across resumed runs: skipped cases carry
            # no fresh timing, so merge instead of replace
            old = existing.get(generator_name, {}).get("timings", {})
            entry["timings"] = {**old, **timings}
        elif "timings" in existing.get(generator_name, {}):
            entry["timings"] = existing[generator_name]["timings"]
        existing[generator_name] = entry

    _locked_merge_json(diag_path, _merge)


def record_outcomes(outcomes, diagnostics: dict) -> dict:
    """Fold run_cases outcomes into the diagnostics dict; returns the
    per-case timing profile ({dir_path: seconds}, generated only)."""
    timings = {}
    for test_case, result, elapsed in outcomes:
        key = result if result != "error" else "errors"
        diagnostics[key] = diagnostics.get(key, 0) + 1
        if result == "generated":
            diagnostics["test_identifiers"].append(test_case.dir_path())
            timings[test_case.dir_path()] = round(elapsed, 4)
    return timings


def run_generator(generator_name: str, providers, args=None) -> dict:
    """CLI + provider loop (reference gen_runner.py:142-301)."""
    parser = argparse.ArgumentParser(
        prog=f"gen-{generator_name}",
        description=f"Generate {generator_name} test vectors")
    parser.add_argument("-o", "--output-dir", required=True,
                        help="output directory (tree root)")
    parser.add_argument("-f", "--force", action="store_true",
                        help="regenerate existing complete cases")
    parser.add_argument("--preset-list", nargs="*", default=None)
    parser.add_argument("--fork-list", nargs="*", default=None)
    parser.add_argument("-c", "--collect-only", action="store_true")
    parser.add_argument("-j", "--workers", type=int, default=None,
                        help="worker processes (default: cpu count, "
                             "capped at 8; 1 = serial)")
    parser.add_argument("--case-batch", action="store_true",
                        help="fold each case's signature checks into one "
                             "RLC pairing (the corpus factory's default; "
                             "off here so the per-generator CLI stays the "
                             "reference-shaped baseline)")
    ns = parser.parse_args(args)
    if ns.workers is None:
        ns.workers = min(8, os.cpu_count() or 1)

    # host tool: its fork pool's workers must not contend for a chip
    import jax
    jax.config.update("jax_platforms", "cpu")

    from consensus_specs_tpu.test_infra import context as ctx
    ctx.DEFAULT_BLS_ACTIVE = True  # generators force real signatures

    diagnostics = {"collected": 0, "generated": 0, "skipped": 0, "errors": 0,
                   "test_identifiers": []}
    cases, diagnostics["collected"] = collect_cases(
        providers, ns.preset_list, ns.fork_list, force=ns.force,
        output_dir=ns.output_dir, collect_only=ns.collect_only)

    if ns.collect_only:
        print(f"collected {diagnostics['collected']} cases")
        return diagnostics

    outcomes, error_log = run_cases(cases, ns.output_dir,
                                    workers=ns.workers, fold=ns.case_batch)
    timings = record_outcomes(outcomes, diagnostics)
    write_run_reports(generator_name, ns.output_dir, diagnostics,
                      error_log, timings=timings)

    print(f"{generator_name}: collected={diagnostics['collected']} "
          f"generated={diagnostics['generated']} "
          f"skipped={diagnostics['skipped']} errors={diagnostics['errors']}")
    if diagnostics["errors"]:
        sys.exit(1)
    return diagnostics
