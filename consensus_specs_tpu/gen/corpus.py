"""Corpus factory: every generator, one warm pool, one invocation.

``make generate_tests`` runs the 19 generators as sequential
``python generators/<name>/main.py`` processes, each re-importing the
spec ladders, rebuilding genesis states, and re-deriving pubkeys.  The
factory inverts that shape:

1. **Collect** — import every generator entrypoint in THIS process and
   gather all providers' cases into one list (each case remembers its
   generator for diagnostics/error-log routing).
2. **Pre-warm** — before any fork, build the spec modules for every
   (fork, preset) the collected cases touch, seed
   ``test_infra.context._state_cache`` with the default-balance genesis
   states, and populate ``keys._pubkey_cache`` (plus the signing memo,
   which workers also inherit).  The runner's fork-start pool already
   ships case INDICES to children (``gen_runner.py``); warm caches ride
   the same copy-on-write parent image, so no worker ever rebuilds
   genesis or re-derives a pubkey.
3. **Schedule** — longest-case-first over ONE shared pool.  The cost
   profile is the per-case ``timings`` maps that
   ``gen_runner.write_run_reports`` persists into
   ``diagnostics_obj.json``; a case without history is assumed
   expensive (scheduled early), so an unknown long case cannot land
   last and stretch the makespan.  Cases are folded
   (``--case-batch`` semantics: one RLC pairing per case, failed folds
   replay synchronously) unless ``--no-fold``.

Byte-fidelity is the replayer's job (``gen/replay.py`` /
``make corpus-check``); the bench (``benchmarks/bench_corpus.py``)
asserts tree-digest identity against the serial per-generator path.
"""
import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Every generator entrypoint exposing a providers() hook.  kzg_4844
# books its diagnostics under "kzg" (its run_generator name); the dict
# maps directory name -> diagnostics name.
GENERATORS = {
    "operations": "operations", "sanity": "sanity", "finality": "finality",
    "rewards": "rewards", "random": "random", "forks": "forks",
    "epoch_processing": "epoch_processing", "genesis": "genesis",
    "ssz_static": "ssz_static", "bls": "bls", "shuffling": "shuffling",
    "light_client": "light_client", "kzg_4844": "kzg",
    "kzg_7594": "kzg_7594", "fork_choice": "fork_choice",
    "merkle_proof": "merkle_proof", "ssz_generic": "ssz_generic",
    "sync": "sync", "transition": "transition",
}

# Default estimate (seconds) for a case with no timing history: above
# nearly every real case, so unknowns schedule first.
UNKNOWN_CASE_COST = 60.0


def _load_entrypoint(gen_dir: str):
    """Import generators/<gen_dir>/main.py under a unique module name
    (they are all called ``main`` and are not a package)."""
    import importlib.util
    path = os.path.join(REPO_ROOT, "generators", gen_dir, "main.py")
    spec = importlib.util.spec_from_file_location(
        f"corpus_gen_{gen_dir}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def collect_corpus_cases(generator_names, preset_list=None, fork_list=None,
                         force=False, output_dir=None):
    """All requested generators' cases, tagged with their generator.

    Returns ``(cases, per_gen_collected)`` where each case has gained a
    ``generator_name`` attribute for report routing."""
    from . import gen_runner
    cases = []
    per_gen = {}
    for gen_dir in generator_names:
        diag_name = GENERATORS[gen_dir]
        mod = _load_entrypoint(gen_dir)
        gen_cases, collected = gen_runner.collect_cases(
            mod.providers(), preset_list, fork_list,
            force=force, output_dir=output_dir)
        for case in gen_cases:
            case.generator_name = diag_name
        cases.extend(gen_cases)
        per_gen[diag_name] = collected
    return cases, per_gen


def load_cost_profile(output_dir: str) -> dict:
    """{case dir_path: seconds} union over every generator's persisted
    ``timings`` map in the output tree's ``diagnostics_obj.json`` —
    prior serial runs and prior corpus runs both contribute."""
    diag_path = os.path.join(output_dir, "diagnostics_obj.json")
    profile = {}
    if os.path.exists(diag_path):
        try:
            with open(diag_path) as f:
                diag = json.load(f)
        except ValueError:
            return profile  # torn/legacy file: schedule without history
        for entry in diag.values():
            if isinstance(entry, dict):
                profile.update(entry.get("timings") or {})
    return profile


def schedule_cases(cases, profile: dict):
    """Longest-first order (classic LPT makespan heuristic for one
    shared pool); unknown cases count as UNKNOWN_CASE_COST so they
    cannot hide at the tail."""
    return sorted(
        cases,
        key=lambda c: profile.get(c.dir_path(), UNKNOWN_CASE_COST),
        reverse=True)


def prewarm(cases, keys_limit=None) -> dict:
    """Warm the parent image the workers will inherit copy-on-write.

    - spec modules for every (exec_fork, preset) the cases touch
    - ``context._state_cache`` genesis blobs for the
      (default_balances, default_activation_threshold) profile on those
      specs — the key nearly every ``spec_state_test`` hits.  Other
      profiles stay lazy: ``large_validator_set`` on mainnet builds
      genuinely huge states, and the low/misc-balance profiles only
      make sense with the thresholds their tests pair them with
    - ``keys._pubkey_cache`` for the first ``keys_limit`` privkeys
      (default: enough for the largest default-balance validator set)

    Returns a summary dict for the log line."""
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.test_infra import context as ctx
    from consensus_specs_tpu.test_infra import keys

    combos = sorted({(c.exec_fork, c.preset_name) for c in cases
                     if c.preset_name in ("minimal", "mainnet")})
    largest_set = 0
    for fork, preset in combos:
        spec = build_spec(fork, preset)
        largest_set = max(largest_set, len(ctx.default_balances(spec)))
        ctx._get_genesis_state(spec, ctx.default_balances,
                               ctx.default_activation_threshold)
    if keys_limit is None:
        keys_limit = largest_set
    for privkey in keys.privkeys[:keys_limit]:
        keys.pubkey(privkey)
    return {"specs": len(combos), "genesis_states": len(ctx._state_cache),
            "pubkeys": keys_limit}


def run_corpus(output_dir: str, generator_names=None, preset_list=None,
               fork_list=None, workers=None, force=False, fold=True,
               prewarm_parent=True) -> dict:
    """The factory: collect -> prewarm -> schedule -> one shared pool.

    Returns the summary dict (also merged into
    ``diagnostics_obj.json`` per generator)."""
    from . import gen_runner
    if generator_names is None:
        generator_names = list(GENERATORS)
    if workers is None:
        workers = min(8, os.cpu_count() or 1)

    t0 = time.time()
    cases, per_gen_collected = collect_corpus_cases(
        generator_names, preset_list, fork_list,
        force=force, output_dir=output_dir)
    t_collect = time.time() - t0

    warm = {}
    if prewarm_parent:
        t1 = time.time()
        warm = prewarm(cases)
        warm["seconds"] = round(time.time() - t1, 2)

    profile = load_cost_profile(output_dir)
    ordered = schedule_cases(cases, profile)
    known = sum(1 for c in cases if c.dir_path() in profile)
    print(f"corpus: {len(cases)} cases from {len(generator_names)} "
          f"generators (collect {t_collect:.1f}s, profile covers "
          f"{known}/{len(cases)}, prewarm {warm or 'off'})")

    t2 = time.time()
    outcomes, error_log = gen_runner.run_cases(
        ordered, output_dir, workers=workers, fold=fold)
    wall = time.time() - t2

    # route outcomes/errors back to their generators' report entries
    summary = {"collected": 0, "generated": 0, "skipped": 0, "errors": 0,
               "cases": len(cases), "wall_seconds": round(wall, 2),
               "workers": workers}
    by_gen = {}
    for case, result, elapsed in outcomes:
        by_gen.setdefault(case.generator_name, []).append(
            (case, result, elapsed))
    for diag_name, gen_outcomes in sorted(by_gen.items()):
        diagnostics = {"collected": per_gen_collected.get(diag_name, 0),
                       "generated": 0, "skipped": 0, "errors": 0,
                       "test_identifiers": []}
        timings = gen_runner.record_outcomes(gen_outcomes, diagnostics)
        gen_errors = [e for e in error_log
                      if any(e["case"] == c.dir_path()
                             for c, _, _ in gen_outcomes)]
        gen_runner.write_run_reports(diag_name, output_dir, diagnostics,
                                     gen_errors, timings=timings)
        for k in ("collected", "generated", "skipped", "errors"):
            summary[k] += diagnostics[k]
    print(f"corpus: generated={summary['generated']} "
          f"skipped={summary['skipped']} errors={summary['errors']} "
          f"in {wall:.1f}s ({workers} workers)")
    return summary


def main(args=None) -> int:
    parser = argparse.ArgumentParser(
        prog="corpus",
        description="Generate the full vector corpus through one shared "
                    "warm worker pool")
    parser.add_argument("-o", "--output-dir", required=True)
    parser.add_argument("-f", "--force", action="store_true",
                        help="regenerate existing complete cases")
    parser.add_argument("--preset-list", nargs="*", default=None)
    parser.add_argument("--fork-list", nargs="*", default=None)
    parser.add_argument("--generators", nargs="*", default=None,
                        choices=sorted(GENERATORS),
                        help="subset of generator names (default: all)")
    parser.add_argument("-j", "--workers", type=int, default=None)
    parser.add_argument("--no-fold", action="store_true",
                        help="disable the per-case RLC signature fold")
    parser.add_argument("--no-prewarm", action="store_true",
                        help="skip parent cache pre-warming")
    ns = parser.parse_args(args)

    # host tool: its fork pool's workers must not contend for a chip
    import jax
    jax.config.update("jax_platforms", "cpu")
    from consensus_specs_tpu.test_infra import context as ctx
    ctx.DEFAULT_BLS_ACTIVE = True

    summary = run_corpus(
        ns.output_dir, generator_names=ns.generators,
        preset_list=ns.preset_list, fork_list=ns.fork_list,
        workers=ns.workers, force=ns.force, fold=not ns.no_fold,
        prewarm_parent=not ns.no_prewarm)
    return 1 if summary["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
