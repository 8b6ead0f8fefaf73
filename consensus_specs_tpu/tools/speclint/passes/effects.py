"""effects pass (E12xx): static effect & concurrency proofs.

The runtime layers prove the hard byte-identity contracts dynamically —
``StateArrays`` fails loud on a direct SSZ write under a pending
deferred column (PR 7), the ``mesh.psums`` counters and jaxpr census
assert the one-psum-per-sub-transition budget (PR 12), the recovery
ladder counts every torn write it degrades on (PR 14).  This pass turns
each of those contracts into a *static proof* over the speclint v2
dataflow framework (``effects.py`` holds the engine), so a violation is
a lint finding before any replay runs:

Commit-scope effect proofs (whole-ladder, interprocedural):

* E1201 — a direct SSZ write to a deferrable column family
  (``balances``, ``inactivity_scores``) reachable inside an open
  ``arrays.commit_scope`` with no store flush before it on the source
  path.
* E1202 — ``fork_state`` reachable inside an open commit scope (forces
  a mid-scope commit; the one-commit-per-epoch contract degrades
  silently).
* E1203 — a checkpoint save reachable inside an open commit scope (the
  class ``CheckpointRefused`` fails loud on at runtime).

Shard-safety race detection (every ``shard_map`` program body in
``parallel/``):

* E1211 — the body reads captured live host state (``sa``/``spec``/
  ``state``/store columns): a cross-shard read outside the declared
  collective points.
* E1212 — host concretization inside the body (``int()``, ``.item()``,
  ``np.*``, ``device_get``).
* E1213 — in-place mutation of a read-only store accessor's return
  (``sa.registry()`` et al.) in the engine consumers: the array
  identity never changes, so cached ``_Cell.shard`` placements keep
  serving the stale column and copy-on-write forks see the mutation.
* E1214 — the static ``PSUM_BUDGET`` census: every reducing program
  holds exactly one (stacked) psum, and every dispatch body's psum sum
  equals the declared per-sub-transition budget.

Happens-before write-ordering (``recovery/`` surfaces; R901's
generalization from call syntax to ordered effect sequences):

* E1221 — a checkpoint blob written after the manifest
  (manifest-written-last is the commit point).
* E1222 — a journal event record after its STEP commit marker, or a
  STEP marker written without a following fsync.
* E1223 — a final-path rename with no preceding fsync
  (``atomic_replace_bytes`` carries a justified ``# noqa``: its
  fencing is the generator's INCOMPLETE-tag protocol).

Baseline: zero findings.  Positive proofs print via
``speclint --effect-verdicts``; the ``CS_TPU_SANITIZER`` runtime mode
(``consensus_specs_tpu/sanitizer.py``, docs/static-analysis.md) arms
the same contracts dynamically — every rule here has an enforcement
twin.
"""
from .. import effects

NAME = "effects"
CODE_PREFIXES = ("E12",)
VERSION = 2
GRANULARITY = "tree"
# dependency-granular cache inputs: everything the analysis reads is
# the project graph's source universe (tools/ excluded exactly as the
# graph excludes it) — edits to tests/, benchmarks/, docs or specs
# markdown leave the cached result warm
INPUT_PREFIXES = ("consensus_specs_tpu/",)
INPUT_EXCLUDE = ("consensus_specs_tpu/tools/",)

SHARD_PREFIX = "consensus_specs_tpu/parallel/"
# engine consumers of the read-only store accessors (E1213)
CONSUMER_PREFIXES = (
    "consensus_specs_tpu/ops/",
    "consensus_specs_tpu/parallel/",
    "consensus_specs_tpu/forkchoice/",
    "consensus_specs_tpu/das/",
)
# durable surfaces: fsync-before-rename applies (E1223)
ORDERING_FSYNC_PREFIXES = ("consensus_specs_tpu/recovery/",)
# ordered-sequence surfaces without the fsync rule (the generator's
# INCOMPLETE-tag protocol fences its bulk outputs instead)
ORDERING_PREFIXES = ORDERING_FSYNC_PREFIXES + (
    "consensus_specs_tpu/sim/repro.py",
    "consensus_specs_tpu/sim/durable.py",
    "consensus_specs_tpu/gen/",
)


def _scope_analysis(ctx):
    memo = getattr(ctx, "_effects_scope_memo", None)
    if memo is None:
        memo = effects.CommitScopeAnalysis(ctx)
        ctx._effects_scope_memo = memo
    return memo


def run(ctx):
    findings = list(_scope_analysis(ctx).findings())
    for rel in ctx.py_files:
        tree = ctx.tree(rel)
        if tree is None:
            continue
        if rel.startswith(SHARD_PREFIX):
            got, _ = effects.analyze_shard_module(rel, tree)
            findings.extend(got)
        if rel.startswith(CONSUMER_PREFIXES):
            findings.extend(
                effects.check_placement_retirement(rel, tree))
        if rel.startswith(ORDERING_PREFIXES):
            got, _ = effects.analyze_ordering(
                rel, tree,
                fsync_scope=rel.startswith(ORDERING_FSYNC_PREFIXES))
            findings.extend(got)
    return findings


def verdict_report(ctx):
    """The positive proofs, one line each (--effect-verdicts)."""
    lines = ["== commit-scope effect proofs =="]
    lines.extend(_scope_analysis(ctx).verdicts())
    lines.append("== shard_map psum census ==")
    for rel in ctx.py_files:
        if not rel.startswith(SHARD_PREFIX):
            continue
        tree = ctx.tree(rel)
        if tree is None:
            continue
        _, verdicts = effects.analyze_shard_module(rel, tree)
        lines.extend(verdicts)
    lines.append("== write-ordering (happens-before) ==")
    for rel in ctx.py_files:
        if not rel.startswith(ORDERING_PREFIXES):
            continue
        tree = ctx.tree(rel)
        if tree is None:
            continue
        _, verdicts = effects.analyze_ordering(
            rel, tree,
            fsync_scope=rel.startswith(ORDERING_FSYNC_PREFIXES))
        lines.extend(verdicts)
    return lines


def check_tree(root):
    """Fixture-corpus convenience (mirrors coverage.check_tree)."""
    from ..driver import Context
    return run(Context(root))
