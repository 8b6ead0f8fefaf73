"""Speclint v2 fixture corpus: the whole-program dataflow framework
(call graph + worklist summaries), the U9xx range prover, the D10xx
determinism pass, the C11xx engine-coverage pass, the SARIF renderer,
the incremental cache, and the --fix autofixer.

Every pass must (a) flag its planted bug, (b) stay quiet on the safe
idiom beside it, and (c) hold its acceptance invariant on the REAL
tree: the coverage pass proves the full contract for every
``faults.SITES`` entry at baseline zero, the range prover certifies
the epoch-kernel subtractions with zero false overflow reports, and
the SARIF output validates against the 2.1.0 schema.
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from consensus_specs_tpu.tools.speclint import (
    cache as sl_cache, dataflow, driver, fixer, sarif)
from consensus_specs_tpu.tools.speclint.graph import ProjectGraph
from consensus_specs_tpu.tools.speclint.passes import (
    coverage, determinism, durability, rangeproof, uint64)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = "consensus_specs_tpu/ops/epoch_kernels.py"


def _codes(findings):
    return [f.code for f in findings]


def _write(root, rel, text):
    path = os.path.join(str(root), rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


# ---------------------------------------------------------------------------
# Project call graph: MRO dispatch, super() chains, install_* wrapping,
# hand-vs-compiled edge parity
# ---------------------------------------------------------------------------

_HAND_BASE = (
    "class Phase0Spec:\n"
    "    fork = 'phase0'\n"
    "    def process_operations(self, state):\n"
    "        return self.helper(state)\n"
    "    def helper(self, state):\n"
    "        return state\n")
_HAND_NEXT = (
    "from consensus_specs_tpu.forks.base import Phase0Spec\n"
    "class AltairSpec(Phase0Spec):\n"
    "    def process_operations(self, state):\n"
    "        self.extra(state)\n"
    "        return super().process_operations(state)\n"
    "    def extra(self, state):\n"
    "        return state\n")
_ACCEL = (
    "def _fast_operations(spec, state):\n"
    "    return kernel(state)\n"
    "def kernel(state):\n"
    "    return state\n"
    "def install_epoch_accel(cls):\n"
    "    cls.process_operations = _fast_operations\n"
    "    setattr(cls, 'helper', kernel)\n")


def _ladder_tree(tmp_path):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/forks/base.py", _HAND_BASE)
    _write(root, "consensus_specs_tpu/forks/altair.py", _HAND_NEXT)
    _write(root, "consensus_specs_tpu/forks/compiled/base.py",
           '"""AUTO-COMPILED from specs/phase0/beacon-chain.md"""\n'
           + _HAND_BASE.replace("Phase0Spec", "CompiledPhase0Spec"))
    _write(root, "consensus_specs_tpu/forks/compiled/altair.py",
           '"""AUTO-COMPILED from specs/altair/beacon-chain.md"""\n'
           + _HAND_NEXT
           .replace("from consensus_specs_tpu.forks.base import Phase0Spec",
                    "from consensus_specs_tpu.forks.compiled.base import "
                    "CompiledPhase0Spec")
           .replace("Phase0Spec", "CompiledPhase0Spec")
           .replace("AltairSpec", "CompiledAltairSpec"))
    _write(root, "consensus_specs_tpu/ops/accel.py", _ACCEL)
    return ProjectGraph(driver.Context(str(root)))


def _edge_names(graph, cls, method):
    fn = graph.classes[cls].methods[method]
    return {c.name for c in graph.callees(fn)}


def test_graph_super_chain_resolves_across_modules(tmp_path):
    g = _ladder_tree(tmp_path)
    # AltairSpec.process_operations -> super() -> the phase0 body, plus
    # the self.extra local dispatch and the installed override
    edges = {(c.cls_name, c.name) for c in g.callees(
        g.classes["AltairSpec"].methods["process_operations"])}
    assert ("Phase0Spec", "process_operations") in edges
    assert ("AltairSpec", "extra") in edges


def test_graph_mro_resolves_inherited_method(tmp_path):
    g = _ladder_tree(tmp_path)
    # helper is defined on the base only; MRO resolution from the
    # subclass must find it
    fn = g.resolve_method("AltairSpec", "helper")
    assert fn is not None and fn.cls_name == "Phase0Spec"
    # super() dispatch starts PAST the class itself
    fn = g.resolve_method("AltairSpec", "process_operations", after=True)
    assert fn.cls_name == "Phase0Spec"


def test_graph_install_wrappers_register_overrides(tmp_path):
    g = _ladder_tree(tmp_path)
    over = {name: {f.name for f in fns}
            for name, fns in g.overrides.items()}
    assert over["process_operations"] == {"_fast_operations"}
    assert over["helper"] == {"kernel"}
    # a self.helper(...) call site therefore reaches the installed
    # kernel as well as the MRO body (the process_operations override
    # itself is an edge of that method's CALLERS, not of its body)
    edges = _edge_names(g, "Phase0Spec", "process_operations")
    assert {"helper", "kernel"} <= edges
    # and the installed wrappers are consensus roots in their own
    # right, so code only an install_* override reaches is still
    # analyzed by the determinism pass
    root_names = {n for _, n in determinism.consensus_roots(g)}
    assert "<installed>.process_operations" in root_names


def test_graph_hand_and_compiled_twins_resolve_identically(tmp_path):
    """Satellite acceptance: the same dispatch shapes (MRO, super()
    chain, install wrapping) must produce isomorphic edges for the
    hand ladder and the compiled ladder."""
    g = _ladder_tree(tmp_path)

    def shape(cls):
        out = {}
        for m in g.classes[cls].methods:
            out[m] = sorted(
                (c.cls_name or "", c.name) for c in
                g.callees(g.classes[cls].methods[m]))
        return out

    def strip(d):
        return {m: [(c.replace("Compiled", ""), n) for c, n in v]
                for m, v in d.items()}

    assert strip(shape("AltairSpec")) == strip(shape("CompiledAltairSpec"))
    assert strip(shape("Phase0Spec")) == strip(shape("CompiledPhase0Spec"))


def test_graph_compiled_provenance_parsed(tmp_path):
    g = _ladder_tree(tmp_path)
    mod = g.modules["consensus_specs_tpu/forks/compiled/altair.py"]
    assert mod.provenance == "specs/altair/beacon-chain.md"
    assert g.modules["consensus_specs_tpu/forks/altair.py"].provenance \
        is None


def test_graph_lazy_module_alias_edges(tmp_path):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/ops/a.py",
           "def entry(x):\n"
           "    from consensus_specs_tpu.ops import b\n"
           "    return b.work(x)\n")
    _write(root, "consensus_specs_tpu/ops/b.py",
           "def work(x):\n    return x\n")
    g = ProjectGraph(driver.Context(str(root)))
    fn = g.modules["consensus_specs_tpu/ops/a.py"].funcs["entry"]
    assert {c.qname for c in g.callees(fn)} \
        == {"consensus_specs_tpu/ops/b.py::work"}


def test_dataflow_worklist_converges():
    """Literal facts propagate through a three-deep call chain and the
    solver stops at the fixed point."""
    edges = {"a": {"b"}, "b": {"c"}, "c": set()}
    base = {"c": {"seed"}, "b": set(), "a": set()}

    def transfer(fn, get):
        out = set(base[fn])
        for callee in edges[fn]:
            out |= get(callee) or set()
        return frozenset(out)

    out = dataflow.solve(["a", "b", "c"], lambda f: edges[f], transfer)
    assert out["a"] == {"seed"}


# ---------------------------------------------------------------------------
# U9xx range prover
# ---------------------------------------------------------------------------

def _verdicts(src):
    [(fn, fr)] = rangeproof.analyze_source(SCOPED, src)
    return {lineno: v for (lineno, _c), (v, _r)
            in fr.sub_verdicts.items()}


def test_ranges_x_minus_x_safe():
    assert _verdicts("def f(seq):\n"
                     "    b = u64_column(seq)\n"
                     "    return b - b\n") == {3: "safe"}


def test_ranges_division_chain_safe():
    """a - a // q with q >= 1: the relational chain, not intervals."""
    src = ("# speclint: invariant: q >= 1\n"
           "def f(b, q):\n"
           "    p = b // q\n"
           "    return b - p\n")
    assert _verdicts(src) == {4: "safe"}


def test_ranges_multiplication_needs_guard_discharge():
    """BRPE * b >= b only holds when the multiply itself cannot wrap —
    the guarded-by-caller pragma (or a _guard call) is the license."""
    body = ("def f(b, brpe, q):\n"
            "    p = b // q\n"
            "    return brpe * b - p\n")
    inv = ("# speclint: invariant: brpe >= 1\n"
           "# speclint: invariant: q >= 1\n")
    assert _verdicts(inv + body)[5] == "unknown"
    pragma = "# speclint: guarded-by-caller (bounded)\n"
    assert _verdicts(pragma + inv + body)[6] == "safe"


def test_ranges_subscript_preserves_relation():
    src = ("# speclint: invariant: q >= 1\n"
           "def f(b, q, idx):\n"
           "    p = b // q\n"
           "    return b[idx] - p[idx]\n")
    assert _verdicts(src) == {4: "safe"}
    # a DIFFERENT index on each side must NOT inherit the relation
    src2 = ("# speclint: invariant: q >= 1\n"
            "def f(b, q, i, j):\n"
            "    p = b // q\n"
            "    return b[i] - p[j]\n")
    assert _verdicts(src2) == {4: "unknown"}


def test_ranges_rebinding_kills_relation():
    src = ("# speclint: invariant: q >= 1\n"
           "def f(b, q, seq):\n"
           "    p = b // q\n"
           "    b = u64_column(seq)\n"
           "    return b - p\n")
    assert _verdicts(src) == {5: "unknown"}


def test_ranges_interval_proof_and_overflow():
    safe = ("# speclint: invariant: a >= 1000\n"
            "# speclint: invariant: b <= 10\n"
            "def f(a, b):\n"
            "    return a - b\n")
    assert _verdicts(safe) == {4: "safe"}
    bad = ("# speclint: invariant: a <= 10\n"
           "# speclint: invariant: b >= 1000\n"
           "def f(a, b):\n"
           "    return a - b\n")
    assert _verdicts(bad) == {4: "overflow"}
    assert "U901" in _codes(rangeproof.check_source(SCOPED, bad))


def test_ranges_invariant_applies_to_opaque_assignment():
    """`prq = int(spec.X)` is opaque; the declared invariant still
    narrows it — the real epoch-kernel shape."""
    src = ("def f(spec, b):\n"
           "    # speclint: invariant: prq >= 1\n"
           "    prq = int(spec.PROPOSER_REWARD_QUOTIENT)\n"
           "    p = b // prq\n"
           "    return b - p\n")
    assert _verdicts(src) == {5: "safe"}


def test_ranges_invariant_errors_are_u902():
    for inv in ("# speclint: invariant: a >=\n",
                "# speclint: invariant: a + b\n",
                "# speclint: invariant: a <= b\n",
                "# speclint: invariant: 5 <= a <= 3\n"):
        src = inv + "def f(a, b):\n    return a\n"
        assert _codes(rangeproof.check_source(SCOPED, src)) == ["U902"], inv
    ok = ("# speclint: invariant: 1 <= a <= MAX_EFFECTIVE_BALANCE\n"
          "def f(a, b):\n    return a\n")
    assert rangeproof.check_source(SCOPED, ok) == []


def test_ranges_redundant_noqa_is_u903():
    src = ("def f(b):\n"
           "    return b - b  # noqa: U101\n")
    findings = rangeproof.check_source(SCOPED, src)
    assert _codes(findings) == ["U903"]
    # a noqa on a genuinely unprovable subtraction is NOT redundant
    src2 = ("def f(b, p):\n"
            "    return b - p  # noqa: U101\n")
    assert rangeproof.check_source(SCOPED, src2) == []


def test_uint64_u101_discharged_by_prover():
    """The integration the pragmas were demoted for: a taint-flagged
    subtraction the prover certifies no longer fires U101."""
    src = ("# speclint: invariant: q >= 1\n"
           "def f(seq, q):\n"
           "    b = u64_column(seq)\n"
           "    p = b // q\n"
           "    return b - p\n")
    assert "U101" not in _codes(uint64.check_source(SCOPED, src))
    unproven = ("def f(seq, q):\n"
                "    b = u64_column(seq)\n"
                "    p = b // q\n"
                "    return b - p\n")   # q >= 1 NOT declared
    assert "U101" in _codes(uint64.check_source(SCOPED, unproven))


def test_real_epoch_kernel_subtractions_proven():
    """Acceptance: the two historically noqa'd epoch-kernel
    subtractions carry machine-checked proofs, their pragmas are gone,
    and the whole scoped tree has zero false overflow reports."""
    with open(os.path.join(REPO, SCOPED)) as f:
        text = f.read()
    assert "noqa: U101" not in text, \
        "the safe-subtraction pragmas were supposed to be demoted"
    results = rangeproof.analyze_source(SCOPED, text)
    proven = {
        (fn.name, lineno): verdict
        for fn, fr in results
        for (lineno, _c), (verdict, _r) in fr.sub_verdicts.items()}
    assert any(fn == "phase0_inactivity_kernel" and v == "safe"
               for (fn, _), v in proven.items())
    assert any(fn == "_phase0_rewards_and_penalties" and v == "safe"
               for (fn, _), v in proven.items())
    ctx = driver.Context(REPO)
    findings = [f for rel in ctx.py_files if rangeproof.in_scope(rel)
                for f in rangeproof.check_source(rel, ctx.source(rel))]
    assert findings == [], \
        f"U9xx must be baseline-zero on the repo: {findings}"


# ---------------------------------------------------------------------------
# D10xx determinism pass
# ---------------------------------------------------------------------------

def _det_tree(tmp_path, helper_body, helper_name="work"):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/forks/foo.py",
           "from consensus_specs_tpu.ops import eng\n"
           "class FooSpec:\n"
           "    def process_thing(self, state):\n"
           f"        return eng.{helper_name}(state)\n")
    _write(root, "consensus_specs_tpu/ops/eng.py", helper_body)
    return driver.Context(str(root))


def test_determinism_flags_set_order_escape(tmp_path):
    ctx = _det_tree(tmp_path,
                    "def work(state):\n"
                    "    s = set(state)\n"
                    "    return list(s)\n")
    findings = determinism.run(ctx)
    assert _codes(findings) == ["D1001"]
    assert "reachable from FooSpec.process_thing" in findings[0].message


def test_determinism_sorted_and_folds_exempt(tmp_path):
    ctx = _det_tree(tmp_path,
                    "def work(state):\n"
                    "    s = set(state)\n"
                    "    total = 0\n"
                    "    for x in s:\n"
                    "        total += x\n"       # order-insensitive fold
                    "    return sorted(s), total\n")
    assert determinism.run(ctx) == []


def test_determinism_collective_folds_exempt(tmp_path):
    """A sink whose value flows DIRECTLY into an order-insensitive fold
    is exempt: host folds (``sum(list(s))``) and the mesh collectives
    (``psum``/``all_gather`` — modular addition over a fixed axis /
    gathered by mesh index, never by arrival order)."""
    ctx = _det_tree(tmp_path,
                    "import jax\n"
                    "import numpy as np\n"
                    "def work(state):\n"
                    "    s = set(state)\n"
                    "    a = sum(list(s))\n"
                    "    b = jax.lax.psum(np.fromiter(s, np.uint64),\n"
                    "                     'validators')\n"
                    "    c = jax.lax.all_gather(np.asarray(list(s)),\n"
                    "                           'validators')\n"
                    "    return a, b, c\n")
    assert determinism.run(ctx) == []


def test_determinism_fold_exemption_is_direct_only(tmp_path):
    """The exemption stops at statement boundaries: materializing the
    unordered list FIRST and folding later still leaks the order (the
    intermediate list is a consensus-visible value)."""
    ctx = _det_tree(tmp_path,
                    "import jax\n"
                    "def work(state):\n"
                    "    s = set(state)\n"
                    "    items = list(s)\n"
                    "    return jax.lax.psum(items, 'v')\n")
    assert _codes(determinism.run(ctx)) == ["D1001"]


def test_determinism_reports_in_parallel_package(tmp_path):
    """The mesh engine (``consensus_specs_tpu/parallel/``) produces
    consensus-visible results: findings there must report."""
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/forks/foo.py",
           "from consensus_specs_tpu.parallel import eng\n"
           "class FooSpec:\n"
           "    def process_thing(self, state):\n"
           "        return eng.work(state)\n")
    _write(root, "consensus_specs_tpu/parallel/eng.py",
           "def work(state):\n"
           "    return state * 0.5\n")
    ctx = driver.Context(str(root))
    findings = determinism.run(ctx)
    assert _codes(findings) == ["D1002"]
    assert findings[0].path == "consensus_specs_tpu/parallel/eng.py"


def test_determinism_flags_order_sensitive_set_loop(tmp_path):
    ctx = _det_tree(tmp_path,
                    "def work(state):\n"
                    "    out = []\n"
                    "    for x in set(state):\n"
                    "        out.append(x)\n"
                    "    return out\n")
    assert _codes(determinism.run(ctx)) == ["D1001"]


def test_determinism_flags_float_and_division(tmp_path):
    ctx = _det_tree(tmp_path,
                    "def work(state):\n"
                    "    half = state * 0.5\n"
                    "    return half + state / 2\n")
    assert _codes(determinism.run(ctx)) == ["D1002", "D1002"]


def test_determinism_flags_ambient_reads(tmp_path):
    ctx = _det_tree(tmp_path,
                    "import os, time, random\n"
                    "def work(state):\n"
                    "    t = time.time()\n"
                    "    r = random.random()\n"
                    "    e = os.environ.get('X')\n"
                    "    return t, r, e, state\n")
    assert _codes(determinism.run(ctx)) == ["D1003", "D1003", "D1003"]


def test_determinism_flags_id_keys_and_builtin_hash(tmp_path):
    ctx = _det_tree(tmp_path,
                    "_CACHE = {}\n"
                    "def work(state):\n"
                    "    _CACHE[id(state)] = 1\n"
                    "    return hash('x')\n")
    assert _codes(determinism.run(ctx)) == ["D1004", "D1005"]


def test_determinism_spec_hash_shadow_exempt(tmp_path):
    ctx = _det_tree(tmp_path,
                    "from consensus_specs_tpu.utils.hash_function "
                    "import hash\n"
                    "def work(state):\n"
                    "    return hash(state)\n")
    assert determinism.run(ctx) == []


def test_determinism_unreachable_code_not_flagged(tmp_path):
    """The reachability half: the same hazard in a function nothing on
    a consensus path calls stays quiet."""
    ctx = _det_tree(tmp_path,
                    "def work(state):\n"
                    "    return state\n"
                    "def bench_helper(state):\n"
                    "    import time\n"
                    "    return time.time()\n")
    assert determinism.run(ctx) == []


def test_determinism_compiled_modules_not_double_reported(tmp_path):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/forks/foo.py",
           "class FooSpec:\n"
           "    def process_thing(self, state):\n"
           "        return state / 2\n")
    _write(root, "consensus_specs_tpu/forks/compiled/foo.py",
           '"""AUTO-COMPILED from specs/foo.md"""\n'
           "class CompiledFooSpec:\n"
           "    def process_thing(self, state):\n"
           "        return state / 2\n")
    findings = determinism.run(driver.Context(str(root)))
    assert _codes(findings) == ["D1002"]
    assert findings[0].path == "consensus_specs_tpu/forks/foo.py"


def test_determinism_real_tree_clean():
    """Acceptance half of satellite 1: after the das table-key,
    env-knob and kzg integer-math fixes, the consensus surface is
    determinism-clean."""
    assert determinism.run(driver.Context(REPO)) == []


def test_determinism_flags_tuple_id_key(tmp_path):
    """D1004 catches an id() call hidden inside a tuple key."""
    ctx = _det_tree(tmp_path,
                    "CACHE = {}\n"
                    "def work(state):\n"
                    "    return CACHE.get((id(state), 4))\n")
    assert _codes(determinism.run(ctx)) == ["D1004"]


def test_determinism_flags_id_tainted_name_key(tmp_path):
    """D1004 catches the two-line shape the sim genesis cache had:
    ``key = (id(x), n)`` then ``d.get(key)``."""
    ctx = _det_tree(tmp_path,
                    "CACHE = {}\n"
                    "def work(state):\n"
                    "    key = (id(state), 4)\n"
                    "    return CACHE.get(key)\n")
    findings = determinism.run(ctx)
    assert _codes(findings) == ["D1004"]


def test_determinism_d1004_reports_in_sim_scope(tmp_path):
    """The sim package is scanned for D1004 regardless of
    consensus-root reachability — but ONLY for D1004: the harness may
    read clocks and RNG by design."""
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/sim/fixture_driver.py",
           "import time\n"
           "CACHE = {}\n"
           "def genesis(spec, n):\n"
           "    key = (id(spec), n)\n"
           "    return CACHE.get(key)\n"
           "def pacing():\n"
           "    return time.time()\n")
    findings = determinism.run(driver.Context(str(root)))
    assert _codes(findings) == ["D1004"]
    assert "sim persistence scope" in findings[0].message


def test_determinism_sim_driver_genesis_cache_clean():
    """Regression for the fixed stale-aliasing bug: the real
    ``sim/driver.py`` genesis cache keys by stable spec identity now —
    zero D1004 findings anywhere under ``consensus_specs_tpu/sim/``."""
    findings = determinism.run(driver.Context(REPO))
    assert [f for f in findings
            if f.path.startswith("consensus_specs_tpu/sim/")] == []


# ---------------------------------------------------------------------------
# R9xx durability pass
# ---------------------------------------------------------------------------

def test_durability_flags_bare_final_path_write(tmp_path):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/recovery/foo.py",
           "import json\n"
           "def dump(path, payload):\n"
           "    with open(path, 'w') as f:\n"
           "        json.dump(payload, f)\n")
    findings = durability.run(driver.Context(str(root)))
    assert _codes(findings) == ["R901"]
    assert "torn file" in findings[0].message


def test_durability_temp_rename_exempt(tmp_path):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/recovery/foo.py",
           "import os\n"
           "def dump(path, data):\n"
           "    with open(path + '.tmp', 'wb') as f:\n"
           "        f.write(data)\n"
           "        os.fsync(f.fileno())\n"
           "    os.replace(path + '.tmp', path)\n")
    assert durability.run(driver.Context(str(root))) == []


def test_durability_atomic_helper_exempt(tmp_path):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/sim/repro.py",
           "from consensus_specs_tpu.recovery.atomic import "
           "atomic_write_json\n"
           "def dump(path, payload):\n"
           "    atomic_write_json(path, payload)\n")
    assert durability.run(driver.Context(str(root))) == []


def test_durability_fsynced_class_journal_exempt(tmp_path):
    """An append-mode journal certified by the fsync in a SIBLING
    method of the same class (the write-ahead journal's shape)."""
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/recovery/journal2.py",
           "import os\n"
           "class J:\n"
           "    def __init__(self, path):\n"
           "        self._f = open(path, 'ab')\n"
           "    def commit(self):\n"
           "        os.fsync(self._f.fileno())\n")
    assert durability.run(driver.Context(str(root))) == []


def test_durability_str_replace_does_not_exempt(tmp_path):
    """Only ``os.replace``/``os.rename``/``os.fsync`` certify the
    discipline — an ordinary str.replace filename slug next to a bare
    write must still flag."""
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/recovery/foo.py",
           "def dump(site, data):\n"
           "    path = site.replace('.', '-') + '.json'\n"
           "    with open(path, 'w') as f:\n"
           "        f.write(data)\n")
    assert _codes(durability.run(driver.Context(str(root)))) == ["R901"]


def test_durability_out_of_scope_quiet(tmp_path):
    """The same bare write outside the persistence scopes is not this
    pass's business."""
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/ops/foo.py",
           "def dump(path, data):\n"
           "    with open(path, 'w') as f:\n"
           "        f.write(data)\n")
    assert durability.run(driver.Context(str(root))) == []


def test_durability_real_tree_clean():
    """Acceptance: after the repro/gen_runner conversions to
    recovery/atomic.py, the persistence scopes carry zero bare
    final-path writes."""
    assert durability.run(driver.Context(REPO)) == []


# ---------------------------------------------------------------------------
# C11xx engine-coverage pass
# ---------------------------------------------------------------------------

_FIXTURE_FAULTS = (
    "class InjectedFault(BaseException):\n"
    "    pass\n"
    "SITES = (\n"
    "    'demo.dispatch',\n"
    ")\n"
    "SITE_SWITCHES = {\n"
    "    'demo.': 'CS_TPU_DEMO',\n"
    "}\n"
    "def check(site):\n    pass\n"
    "def count_fallback(series, exc=None, organic='guard', site=None):\n"
    "    pass\n")

# the epoch shape: the literal flows through a shared helper's
# parameter, so proving the contract REQUIRES the interprocedural
# literal-flow solve
_FIXTURE_ENGINE = (
    "from consensus_specs_tpu import faults, supervisor\n"
    "def _supervised(spec, state, site, fast_fn):\n"
    "    if not supervisor.admit(site):\n"
    "        return False\n"
    "    try:\n"
    "        faults.check(site)\n"
    "        fast_fn(state)\n"
    "    except faults.InjectedFault as exc:\n"
    "        faults.count_fallback(_F, exc, site=site)\n"
    "        return False\n"
    "    return True\n"
    "def try_demo(spec, state):\n"
    "    return _supervised(spec, state, 'demo.dispatch', kernel)\n"
    "def kernel(state):\n"
    "    return state\n")
_FIXTURE_TEST = (
    "def test_demo_differential():\n"
    "    assert 'demo.dispatch'\n")
_FIXTURE_WORKFLOW = (
    "jobs:\n"
    "  off-leg:\n"
    "    steps:\n"
    "      - run: CS_TPU_DEMO=0 python -m pytest tests/ -q\n")


def _cov_tree(tmp_path, *, faults_text=_FIXTURE_FAULTS,
              engine=_FIXTURE_ENGINE, test=_FIXTURE_TEST,
              workflow=_FIXTURE_WORKFLOW):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/faults.py", faults_text)
    if engine is not None:
        _write(root, "consensus_specs_tpu/ops/eng.py", engine)
    if test is not None:
        _write(root, "tests/test_demo.py", test)
    if workflow is not None:
        _write(root, ".github/workflows/run-tests.yml", workflow)
    return str(root)


def test_coverage_full_contract_is_clean(tmp_path):
    assert coverage.check_tree(_cov_tree(tmp_path)) == []


def test_coverage_missing_each_leg_fires(tmp_path):
    # no dispatch at all: C1101/C1102/C1103/C1104 in one shot
    codes = _codes(coverage.check_tree(_cov_tree(
        tmp_path, engine="def unrelated():\n    pass\n")))
    assert {"C1101", "C1102", "C1103", "C1104"} <= set(codes)

    # counted fallback dropped
    no_count = _FIXTURE_ENGINE.replace(
        "        faults.count_fallback(_F, exc, site=site)\n", "")
    codes = _codes(coverage.check_tree(
        _cov_tree(tmp_path / "b", engine=no_count)))
    assert codes == ["C1102"]

    # supervisor gate dropped
    no_admit = _FIXTURE_ENGINE.replace(
        "    if not supervisor.admit(site):\n"
        "        return False\n", "    pass\n")
    codes = _codes(coverage.check_tree(
        _cov_tree(tmp_path / "c", engine=no_admit)))
    assert codes == ["C1103"]

    # fallback handler dropped (count moved out of a handler)
    no_handler = (
        "from consensus_specs_tpu import faults, supervisor\n"
        "def try_demo(spec, state):\n"
        "    site = 'demo.dispatch'\n"
        "    supervisor.admit(site)\n"
        "    faults.check(site)\n"
        "    faults.count_fallback(_F, None, site=site)\n")
    codes = _codes(coverage.check_tree(
        _cov_tree(tmp_path / "d", engine=no_handler)))
    assert codes == ["C1104"]

    # differential test reference dropped
    codes = _codes(coverage.check_tree(
        _cov_tree(tmp_path / "e", test="def test_other():\n    pass\n")))
    assert codes == ["C1105"]

    # CI off-leg dropped
    codes = _codes(coverage.check_tree(_cov_tree(
        tmp_path / "f",
        workflow=_FIXTURE_WORKFLOW.replace("CS_TPU_DEMO=0", ""))))
    assert codes == ["C1106"]


def test_coverage_site_without_switch_family(tmp_path):
    faults_text = _FIXTURE_FAULTS.replace(
        "    'demo.': 'CS_TPU_DEMO',\n", "    'other.': 'CS_TPU_OTHER',\n")
    codes = _codes(coverage.check_tree(
        _cov_tree(tmp_path, faults_text=faults_text)))
    assert "C1100" in codes


def test_coverage_unregistered_site_is_c1107(tmp_path):
    rogue = _FIXTURE_ENGINE + (
        "def try_rogue(spec, state):\n"
        "    return _supervised(spec, state, 'rogue.site', kernel)\n")
    findings = coverage.check_tree(_cov_tree(tmp_path, engine=rogue))
    assert [f.code for f in findings] == ["C1107"]
    assert "rogue.site" in findings[0].message
    assert findings[0].path == "consensus_specs_tpu/ops/eng.py"


def test_coverage_findings_anchor_at_sites_tuple(tmp_path):
    findings = coverage.check_tree(_cov_tree(
        tmp_path, test="def test_other():\n    pass\n"))
    (f,) = findings
    assert f.path == "consensus_specs_tpu/faults.py"
    assert f.line == 4      # the 'demo.dispatch' tuple entry line


def test_coverage_absent_faults_module_is_quiet(tmp_path):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/ops/eng.py", "x = 1\n")
    assert coverage.check_tree(str(root)) == []


def test_coverage_real_tree_baseline_zero():
    """THE acceptance criterion: every faults.SITES entry proves the
    full contract — dispatch + counted fallback + supervisor gate +
    degradation handler + differential reference + CI off-leg — on the
    real tree, with nothing noqa'd or baselined."""
    findings = coverage.run(driver.Context(REPO))
    assert findings == [], "\n".join(f.render() for f in findings)
    # and non-vacuously: the solver really resolved every site
    from consensus_specs_tpu import faults
    graph = driver.Context(REPO).project_graph()
    site_facts, _ = coverage.solve_site_facts(graph)
    for site in faults.SITES:
        assert {"check", "count", "admit", "handler"} \
            <= site_facts.get(site, set()), site


# ---------------------------------------------------------------------------
# SARIF output
# ---------------------------------------------------------------------------

def test_sarif_real_run_validates():
    ctx = driver.Context(REPO)
    findings = driver.run_passes(ctx)
    baseline = driver.load_baseline(os.path.join(REPO,
                                                 driver.BASELINE_NAME))
    new, baselined, _ = driver.apply_baseline(findings, baseline)
    log = sarif.to_sarif(new, baselined)
    assert log["version"] == "2.1.0"
    assert sarif.validate(log) == []
    # the recorded debt must surface as unchanged results (plus any
    # `absent` markers for baseline keys whose findings are fixed)
    states = {r["baselineState"] for r in log["runs"][0]["results"]}
    assert states <= {"new", "unchanged", "absent"} \
        and "unchanged" in states
    rule_ids = {r["id"] for r in log["runs"][0]["tool"]["driver"]["rules"]}
    assert {r["ruleId"] for r in log["runs"][0]["results"]} <= rule_ids


def test_sarif_validator_rejects_malformed():
    assert sarif.validate({"version": "1.0", "runs": []}) != []
    assert sarif.validate({"version": "2.1.0"}) != []
    bad = sarif.to_sarif([], [])
    bad["runs"][0]["results"] = [{"message": {}}]
    assert sarif.validate(bad) != []


def test_sarif_driver_format(tmp_path, capsys):
    root = tmp_path / "repo"
    _write(root, SCOPED,
           "def f(seq):\n"
           "    b = u64_column(seq)\n"
           "    p = u64_column(seq)\n"
           "    return b - p\n")
    rc = driver.main([str(root), "--passes", "uint64", "--format",
                      "sarif", "--no-baseline"])
    assert rc == 1
    log = json.loads(capsys.readouterr().out)
    assert sarif.validate(log) == []
    (result,) = log["runs"][0]["results"]
    assert result["ruleId"] == "U101"
    assert result["baselineState"] == "new"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == SCOPED
    assert loc["region"]["startLine"] == 4


# ---------------------------------------------------------------------------
# Incremental cache
# ---------------------------------------------------------------------------

def _cache_stats(root, *args):
    c = sl_cache.AnalysisCache(os.path.join(root, sl_cache.CACHE_NAME),
                               "salt")
    return c


def test_cache_warm_run_reuses_everything(tmp_path):
    root = tmp_path / "repo"
    _write(root, SCOPED,
           "def f(seq):\n"
           "    b = u64_column(seq)\n"
           "    p = u64_column(seq)\n"
           "    return b - p\n")
    assert driver.main([str(root), "--no-baseline"]) == 1
    ctx = driver.Context(str(root))
    cache = sl_cache.AnalysisCache(
        os.path.join(str(root), sl_cache.CACHE_NAME),
        driver._pass_salt())
    findings = driver.run_passes(ctx, cache=cache)
    assert cache.stats["file_misses"] == 0
    assert cache.stats["tree_misses"] == 0
    assert [f.code for f in findings] == ["U101"]


def test_cache_invalidates_on_edit_and_salt(tmp_path):
    root = tmp_path / "repo"
    _write(root, SCOPED, "def f(seq):\n    return u64_column(seq)\n")
    _write(root, "consensus_specs_tpu/utils/other.py", "x = 1\n")
    assert driver.main([str(root)]) == 0
    # edit ONE file: only its entries miss; the other file stays warm
    _write(root, SCOPED,
           "def f(seq):\n"
           "    b = u64_column(seq)\n"
           "    p = u64_column(seq)\n"
           "    return b - p\n")
    ctx = driver.Context(str(root))
    cache = sl_cache.AnalysisCache(
        os.path.join(str(root), sl_cache.CACHE_NAME),
        driver._pass_salt())
    findings = driver.run_passes(ctx, cache=cache)
    assert [f.code for f in findings] == ["U101"]
    assert cache.stats["file_hits"] > 0          # the untouched file
    assert cache.stats["file_misses"] > 0        # the edited one
    assert cache.stats["tree_misses"] > 0        # tree fingerprint moved
    # a salt change (pass version bump) drops the whole store
    stale = sl_cache.AnalysisCache(
        os.path.join(str(root), sl_cache.CACHE_NAME), "other-salt")
    assert stale.get_file(SCOPED, ctx.sha(SCOPED), "uint64") is None


def test_cache_findings_roundtrip_suppression(tmp_path):
    """Cached findings are pre-noqa; the driver re-applies suppression
    after retrieval, so a cache hit behaves exactly like a fresh run."""
    root = tmp_path / "repo"
    _write(root, SCOPED,
           "def f(seq):\n"
           "    b = u64_column(seq)\n"
           "    p = u64_column(seq)\n"
           "    return b - p  # noqa: U101\n")
    assert driver.main([str(root), "--no-baseline"]) == 0
    assert driver.main([str(root), "--no-baseline"]) == 0   # warm


# ---------------------------------------------------------------------------
# --fix autofixer
# ---------------------------------------------------------------------------

def test_fix_u103_adds_dtype():
    src = ("import numpy as np\n"
           "def f(mask):\n"
           "    n = mask.sum()\n"
           "    k = mask.sum(dtype=np.int64)\n")
    fixed, n = fixer.fix_u103(SCOPED, src)
    assert n == 1
    assert "mask.sum(dtype=np.int64)\n    k" in fixed
    # idempotent + out-of-scope untouched
    assert fixer.fix_u103(SCOPED, fixed) == (fixed, 0)
    assert fixer.fix_u103("consensus_specs_tpu/sim/x.py", src)[1] == 0


def test_fix_noqa_normalizes_real_comments_only():
    src = ("x = 1  #noqa:u101,j203\n"
           "y = 2  # NOQA\n"
           'DOC = """example: #noqa:u101 stays as-is"""\n')
    fixed, n = fixer.fix_noqa(src)
    assert "x = 1  # noqa: U101, J203\n" in fixed
    assert "y = 2  # noqa\n" in fixed
    assert '#noqa:u101 stays as-is' in fixed      # docstring untouched
    assert n == 2
    assert fixer.fix_noqa(fixed) == (fixed, 0)    # idempotent


def test_fix_noqa_keeps_justification_text():
    src = "b = a - c  # noqa: u101 with a bound argument\n"
    fixed, n = fixer.fix_noqa(src)
    assert fixed == "b = a - c  # noqa: U101 with a bound argument\n"
    assert n == 1
    # an unparsable code list is left alone, not mangled
    weird = "x = 1  # noqa: D100x\n"
    assert fixer.fix_noqa(weird) == (weird, 0)


def test_fix_import_hoist_removes_redundant_only():
    src = ("import hashlib\n"
           "def f(x):\n"
           "    import hashlib\n"
           "    return hashlib.sha256(x)\n"
           "def g(x):\n"
           "    import secrets\n"        # NOT at top: deliberate lazy
           "    return secrets.token_bytes(4)\n")
    fixed, n = fixer.fix_import_hoist("m.py", src)
    assert n == 1
    assert fixed.count("import hashlib") == 1
    assert "    import secrets" in fixed          # lazy import kept
    assert fixer.fix_import_hoist("m.py", fixed) == (fixed, 0)


def test_fix_tree_end_to_end(tmp_path):
    root = tmp_path / "repo"
    messy = ("import numpy as np\n"
             "def f(mask):\n"
             "    import numpy as np  # kept: aliased, not plain\n"
             "    return mask.sum()  #noqa:u103\n")
    _write(root, SCOPED, messy)
    _write(root, "tests/test_fixture.py", "S = 'x = 1  #noqa:u101'\n")
    rc = driver.main([str(root), "--fix"])
    assert rc == 0
    with open(os.path.join(str(root), SCOPED)) as f:
        fixed = f.read()
    assert "mask.sum(dtype=np.int64)  # noqa: U103" in fixed
    # tests/ fixtures excluded
    with open(os.path.join(str(root), "tests/test_fixture.py")) as f:
        assert f.read() == "S = 'x = 1  #noqa:u101'\n"
    # second --fix is a no-op
    driver.main([str(root), "--fix"])
    with open(os.path.join(str(root), SCOPED)) as f:
        assert f.read() == fixed


def test_fix_is_noop_on_real_tree():
    """The repo itself carries no mechanically-fixable debt (and --fix
    must never churn it)."""
    from consensus_specs_tpu.tools.speclint.astutil import is_generated
    ctx = driver.Context(REPO)
    for rel in ctx.py_files:
        if rel.startswith(fixer._FIX_EXCLUDE):
            continue
        text = ctx.source(rel)
        if is_generated(text):
            continue
        fixed, _counts = fixer.fix_text(rel, text)
        assert fixed == text, f"--fix would modify {rel}"


# ---------------------------------------------------------------------------
# driver surface
# ---------------------------------------------------------------------------

def test_range_verdicts_cli(capsys):
    assert driver.main([REPO, "--range-verdicts"]) == 0
    out = capsys.readouterr().out
    assert "phase0_inactivity_kernel" in out
    assert "[safe]" in out


def test_baseline_guard_matches_conftest_contract():
    """The checked-in ratchet file satisfies the conftest deflake
    guard's invariants (sorted, deduped, positive counts)."""
    path = os.path.join(REPO, "speclint_baseline.json")
    with open(path) as f:
        raw = f.read()
    pairs = json.loads(
        raw, object_pairs_hook=lambda ps: ps)
    # top-level: comment + counts
    counts = dict(pairs)["counts"]
    keys = [k for k, _ in counts]
    assert keys == sorted(keys)
    assert len(keys) == len(set(keys))
    assert all(isinstance(v, int) and v >= 1 for _, v in counts)


# ---------------------------------------------------------------------------
# review regressions
# ---------------------------------------------------------------------------

def test_ranges_inplace_mutation_kills_stale_interval():
    """Review regression: `pen[idx] += big` (and np.add.at) must
    invalidate pen's abstract value — a later `rewards - pen` was
    falsely proven safe against pen's stale zeros() interval."""
    src = ("import numpy as np\n"
           "def f(seq, idx, big):\n"
           "    rewards = u64_column(seq)\n"
           "    pen = np.zeros(4, dtype=np.uint64)\n"
           "    pen[idx] += big\n"
           "    return rewards - pen\n")
    assert _verdicts(src)[6] == "unknown"
    assert "U101" in _codes(uint64.check_source(SCOPED, src))
    scatter = ("import numpy as np\n"
               "def f(seq, idx, big):\n"
               "    rewards = u64_column(seq)\n"
               "    pen = np.zeros(4, dtype=np.uint64)\n"
               "    np.add.at(pen, idx, big)\n"
               "    return rewards - pen\n")
    assert _verdicts(scatter)[6] == "unknown"
    # while an UNtouched zeros() interval still proves safe
    clean = ("import numpy as np\n"
             "def f(seq):\n"
             "    rewards = u64_column(seq)\n"
             "    pen = np.zeros(4, dtype=np.uint64)\n"
             "    return rewards - pen\n")
    assert _verdicts(clean)[5] == "safe"


def test_coverage_handler_in_caller_of_literal_dispatch(tmp_path):
    """Review regression: an engine whose helper dispatches the site
    literal INLINE (no site parameter) with the fallback handler in
    the caller must still prove the C1104 leg."""
    engine = (
        "from consensus_specs_tpu import faults, supervisor\n"
        "def _dispatch(state):\n"
        "    supervisor.admit('demo.dispatch')\n"
        "    faults.check('demo.dispatch')\n"
        "    return state\n"
        "def entry(state):\n"
        "    try:\n"
        "        return _dispatch(state)\n"
        "    except faults.InjectedFault as exc:\n"
        "        faults.count_fallback(_F, exc, site='demo.dispatch')\n"
        "        return state\n")
    assert coverage.check_tree(_cov_tree(tmp_path, engine=engine)) == []


def test_fix_import_hoist_never_empties_a_body():
    """Review regression: deleting a function's only statement (or all
    of them) must not emit an unparsable empty body."""
    import ast as _ast
    sole = ("import os\n"
            "def probe():\n"
            "    import os\n")
    fixed, n = fixer.fix_import_hoist("m.py", sole)
    _ast.parse(fixed)
    assert n == 0 and "def probe():" in fixed
    double = ("import os\n"
              "import sys\n"
              "def probe():\n"
              "    import os\n"
              "    import sys\n")
    fixed, n = fixer.fix_import_hoist("m.py", double)
    _ast.parse(fixed)
    assert n == 1     # one deleted, one kept so the body stays valid


def test_ranges_memo_shared_between_passes(tmp_path):
    """Review cleanup: one FunctionRanges per function per run — the
    uint64 discharge and the U9xx pass share the Context memo."""
    root = tmp_path / "repo"
    _write(root, SCOPED,
           "def f(seq):\n"
           "    b = u64_column(seq)\n"
           "    return b - b\n")
    ctx = driver.Context(str(root))
    ctx.ranges_memo = {}
    assert uint64.check_file(ctx, SCOPED) == []
    assert rangeproof.check_file(ctx, SCOPED) == []
    assert len(ctx.ranges_memo) == 1      # analyzed once, served twice


# ---------------------------------------------------------------------------
# E12xx effects pass: commit-scope proofs, shard safety, write ordering
# ---------------------------------------------------------------------------

import ast as _e_ast
import shutil
import subprocess
import time

from consensus_specs_tpu.tools.speclint import effects as fx
from consensus_specs_tpu.tools.speclint.passes import effects as effects_pass

_FX_ARRAYS = (
    "def flush(state):\n    pass\n"
    "def commit_scope(state):\n    pass\n"
    "def fork_state(state):\n    pass\n")

_FX_ENGINE_GUARDED = (
    "from consensus_specs_tpu.state import arrays as state_arrays\n"
    "class DemoSpec:\n"
    "    def process_slots(self, state):\n"
    "        with state_arrays.commit_scope(state):\n"
    "            self.process_epoch(state)\n"
    "    def process_epoch(self, state):\n"
    "        self.process_rewards(state)\n"
    "    def process_rewards(self, state):\n"
    "        if try_fast(self, state):\n"
    "            return\n"
    "        self.apply(state)\n"
    "    def apply(self, state):\n"
    "        state.balances[0] += 1\n"
    "def try_fast(spec, state):\n"
    "    state_arrays.flush(state)\n"
    "    return False\n")


def _fx_tree(tmp_path, engine=_FX_ENGINE_GUARDED, arrays_src=_FX_ARRAYS,
             extra=()):
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/state/arrays.py", arrays_src)
    _write(root, "consensus_specs_tpu/forks/demo.py", engine)
    for rel, text in extra:
        _write(root, rel, text)
    return str(root)


def test_e1201_guarded_ladder_is_clean(tmp_path):
    assert effects_pass.check_tree(_fx_tree(tmp_path)) == []


def test_e1201_unguarded_write_escapes_scope(tmp_path):
    engine = _FX_ENGINE_GUARDED.replace(
        "        if try_fast(self, state):\n"
        "            return\n", "")
    findings = effects_pass.check_tree(_fx_tree(tmp_path, engine=engine))
    assert [f.code for f in findings] == ["E1201"]
    # anchored at the write site, deep in the interprocedural closure
    assert findings[0].path == "consensus_specs_tpu/forks/demo.py"
    assert "balances" in findings[0].message


def test_e1201_flush_in_callee_guards_later_write(tmp_path):
    # the guard flows through a transitively-flushing callee: try_fast
    # flushes inside _supervised-style helpers two levels down
    engine = _FX_ENGINE_GUARDED.replace(
        "def try_fast(spec, state):\n"
        "    state_arrays.flush(state)\n"
        "    return False\n",
        "def try_fast(spec, state):\n"
        "    return _inner(state)\n"
        "def _inner(state):\n"
        "    state_arrays.flush(state)\n"
        "    return False\n")
    assert effects_pass.check_tree(_fx_tree(tmp_path, engine=engine)) == []


def test_e1201_write_before_flush_still_fires(tmp_path):
    engine = _FX_ENGINE_GUARDED.replace(
        "    def process_rewards(self, state):\n"
        "        if try_fast(self, state):\n"
        "            return\n"
        "        self.apply(state)\n",
        "    def process_rewards(self, state):\n"
        "        self.apply(state)\n"
        "        try_fast(self, state)\n")
    findings = effects_pass.check_tree(_fx_tree(tmp_path, engine=engine))
    assert [f.code for f in findings] == ["E1201"]


def test_e1201_noqa_suppresses_through_driver(tmp_path):
    engine = _FX_ENGINE_GUARDED.replace(
        "        if try_fast(self, state):\n"
        "            return\n", "").replace(
        "        state.balances[0] += 1\n",
        "        state.balances[0] += 1  # noqa: E1201\n")
    root = _fx_tree(tmp_path, engine=engine)
    assert driver.main([root, "--passes", "effects", "--no-baseline"]) == 0


def test_e1201_opted_out_class_excluded(tmp_path):
    engine = _FX_ENGINE_GUARDED + (
        "class CustodySpec(DemoSpec):\n"
        "    _defer_epoch_commits = False\n"
        "    def process_epoch(self, state):\n"
        "        state.balances[0] += 2\n")
    assert effects_pass.check_tree(_fx_tree(tmp_path, engine=engine)) == []


_FX_INSTALLED_KERNELS = (
    "from consensus_specs_tpu.state import arrays as state_arrays\n"
    "def try_fast(spec, state):\n"
    "    state_arrays.flush(state)\n"
    "    return False\n"
    "_TRY_BY_NAME = {'process_rewards': try_fast}\n")

_FX_INSTALLED_ENGINE = (
    "from consensus_specs_tpu.state import arrays as state_arrays\n"
    "from consensus_specs_tpu.forks import register_fork\n"
    "@register_fork('demo')\n"
    "class DemoSpec:\n"
    "    def process_slots(self, state):\n"
    "        with state_arrays.commit_scope(state):\n"
    "            self.process_epoch(state)\n"
    "    def process_epoch(self, state):\n"
    "        self.process_rewards(state)\n"
    "    def process_rewards(self, state):\n"
    "        self.apply(state)\n"
    "    def apply(self, state):\n"
    "        state.balances[0] += 1\n")


@pytest.mark.parametrize("case,codes", [
    # the install wrapper's dispatch flushes before the spec-shaped body
    ("installed", []),
    # no register_fork: nothing wraps the body, its write escapes
    ("not_registered", ["E1201"]),
    # a dispatch that declines without flushing guards nothing
    ("dispatch_without_flush", ["E1201"]),
])
def test_e1201_install_wrapper_guards_spec_shaped_body(tmp_path, case,
                                                       codes):
    engine, kernels = _FX_INSTALLED_ENGINE, _FX_INSTALLED_KERNELS
    if case == "not_registered":
        engine = engine.replace("@register_fork('demo')\n", "")
    elif case == "dispatch_without_flush":
        kernels = kernels.replace("    state_arrays.flush(state)\n", "")
    root = _fx_tree(tmp_path, engine=engine, extra=[
        ("consensus_specs_tpu/ops/epoch_kernels.py", kernels)])
    findings = effects_pass.check_tree(root)
    assert [f.code for f in findings] == codes


def test_e1202_fork_state_in_scope(tmp_path):
    engine = _FX_ENGINE_GUARDED.replace(
        "    def process_epoch(self, state):\n",
        "    def process_epoch(self, state):\n"
        "        state_arrays.fork_state(state)\n")
    findings = effects_pass.check_tree(_fx_tree(tmp_path, engine=engine))
    assert [f.code for f in findings] == ["E1202"]


def test_e1203_checkpoint_save_in_scope(tmp_path):
    engine = _FX_ENGINE_GUARDED.replace(
        "    def process_epoch(self, state):\n",
        "    def process_epoch(self, state):\n"
        "        cs.save(state)\n")
    ckpt = ("class CheckpointStore:\n"
            "    def save(self, sim):\n"
            "        return 1\n")
    findings = effects_pass.check_tree(_fx_tree(
        tmp_path, engine=engine,
        extra=[("consensus_specs_tpu/recovery/checkpoint.py", ckpt)]))
    assert [f.code for f in findings] == ["E1203"]


# -- shard safety -----------------------------------------------------------

_FX_SHARD = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "from jax import shard_map\n"
    "PSUM_BUDGET = {'demo': 1}\n"
    "def _p_sums(mesh):\n"
    "    def build():\n"
    "        def local(eff):\n"
    "            return jax.lax.psum(jnp.sum(eff), 'v')\n"
    "        return jax.jit(shard_map(local, mesh=mesh))\n"
    "    return build()\n"
    "def _dispatch(spec, state, sub, fast):\n"
    "    return fast(spec, state, None)\n"
    "def try_demo(spec, state):\n"
    "    def fast(spec, state, sa):\n"
    "        prog = _p_sums(state)\n"
    "        return True\n"
    "    return _dispatch(spec, state, 'demo', fast)\n")

_SHARD_REL = "consensus_specs_tpu/parallel/prog.py"


def _shard_findings(src):
    return fx.analyze_shard_module(_SHARD_REL, _e_ast.parse(src))


def test_e1214_budget_proven_on_fixture():
    findings, verdicts = _shard_findings(_FX_SHARD)
    assert findings == []
    assert any("[PROVEN]" in v and "demo" in v for v in verdicts)


def test_e1214_budget_mismatch_fires():
    src = _FX_SHARD.replace("PSUM_BUDGET = {'demo': 1}",
                            "PSUM_BUDGET = {'demo': 0}")
    findings, _ = _shard_findings(src)
    assert "E1214" in [f.code for f in findings]


def test_e1214_stacked_psum_discipline():
    src = _FX_SHARD.replace(
        "            return jax.lax.psum(jnp.sum(eff), 'v')\n",
        "            a = jax.lax.psum(jnp.sum(eff), 'v')\n"
        "            b = jax.lax.psum(jnp.max(eff), 'v')\n"
        "            return a + b\n")
    findings, _ = _shard_findings(src)
    codes = [f.code for f in findings]
    assert codes.count("E1214") >= 2     # >1 psum per program + != budget


def test_e1214_unbudgeted_sub_and_stale_entry():
    src = _FX_SHARD.replace("'demo', fast", "'other', fast")
    findings, _ = _shard_findings(src)
    msgs = " ".join(f.message for f in findings)
    assert "'other'" in msgs and "stale" in msgs


def test_e1211_captured_host_state_in_body():
    src = (
        "from jax import shard_map\n"
        "def _p_bad(mesh, sa):\n"
        "    cols = sa.registry()\n"
        "    def build():\n"
        "        def local(eff):\n"
        "            return eff + cols['eff']\n"
        "        return shard_map(local, mesh=mesh)\n"
        "    return build()\n")
    findings, _ = _shard_findings(src)
    assert [f.code for f in findings] == ["E1211"]
    assert "cols" in findings[0].message


def test_e1211_static_config_capture_is_clean():
    src = (
        "from jax import shard_map\n"
        "def _p_ok(mesh, static):\n"
        "    (increment, in_leak) = static\n"
        "    weights = (1, 2, 3)\n"
        "    def build():\n"
        "        import jax.numpy as jnp\n"
        "        def local(eff):\n"
        "            return eff * jnp.uint64(weights[0] + increment)\n"
        "        return shard_map(local, mesh=mesh)\n"
        "    return build()\n")
    findings, _ = _shard_findings(src)
    assert findings == []


def test_e1212_host_concretization_in_body():
    src = (
        "import numpy as np\n"
        "from jax import shard_map\n"
        "def _p_bad(mesh):\n"
        "    def build():\n"
        "        def local(eff):\n"
        "            n = int(eff.sum())\n"
        "            return np.asarray(eff) * n\n"
        "        return shard_map(local, mesh=mesh)\n"
        "    return build()\n")
    findings, _ = _shard_findings(src)
    assert [f.code for f in findings] == ["E1212", "E1212"]


def test_e1213_inplace_accessor_mutation():
    src = (
        "def bad(sa):\n"
        "    b = sa.balances()\n"
        "    b[0] = 1\n"
        "def bad_view(sa):\n"
        "    cols = sa.registry()\n"
        "    eff = cols['eff']\n"
        "    eff[2] += 1\n"
        "def good_copy(sa):\n"
        "    b = sa.balances().copy()\n"
        "    b[0] = 1\n"
        "def sanctioned(sa, new):\n"
        "    sa.registry_writable()['eff'] = new\n")
    findings = fx.check_placement_retirement(
        "consensus_specs_tpu/ops/consumer.py", _e_ast.parse(src))
    assert [f.code for f in findings] == ["E1213", "E1213"]
    assert findings[0].line == 3 and findings[1].line == 7


# -- write ordering ---------------------------------------------------------

_ORD_REL = "consensus_specs_tpu/recovery/writer.py"


def _ordering(src, fsync_scope=True):
    return fx.analyze_ordering(_ORD_REL, _e_ast.parse(src),
                               fsync_scope=fsync_scope)


def test_e1221_manifest_last_proven_and_violated():
    good = (
        "def write_gen(cs, gen):\n"
        "    atomic_write_bytes(cs.blob_path(gen, 'a'), b'')\n"
        "    atomic_write_bytes(cs.blob_path(gen, 'b'), b'')\n"
        "    atomic_write_json(cs.manifest_path(gen), {})\n")
    findings, verdicts = _ordering(good)
    assert findings == []
    assert any("manifest-written-last" in v for v in verdicts)
    bad = (
        "def write_gen(cs, gen):\n"
        "    atomic_write_json(cs.manifest_path(gen), {})\n"
        "    atomic_write_bytes(cs.blob_path(gen, 'a'), b'')\n")
    findings, _ = _ordering(bad)
    assert [f.code for f in findings] == ["E1221"]


def test_e1222_record_after_step_marker():
    bad = (
        "def drive(journal, step):\n"
        "    journal.commit_step(0, step)\n"
        "    journal.append(BLOCK, b'')\n")
    findings, _ = _ordering(bad)
    assert [f.code for f in findings] == ["E1222"]
    good = bad.replace(
        "    journal.commit_step(0, step)\n    journal.append(BLOCK, b'')\n",
        "    journal.append(BLOCK, b'')\n    journal.commit_step(0, step)\n")
    findings, verdicts = _ordering(good)
    assert findings == []
    assert any("precede their STEP commit marker" in v for v in verdicts)


def test_e1222_step_writer_must_fsync():
    bad = (
        "import os\n"
        "STEP = 5\n"
        "def frame(kind, payload):\n"
        "    return payload\n"
        "class J:\n"
        "    def commit_step(self, ordinal):\n"
        "        self._f.write(frame(STEP, b''))\n")
    findings, _ = _ordering(bad)
    assert [f.code for f in findings] == ["E1222"]
    good = bad.replace(
        "        self._f.write(frame(STEP, b''))\n",
        "        self._f.write(frame(STEP, b''))\n"
        "        os.fsync(self._f.fileno())\n")
    findings, verdicts = _ordering(good)
    assert findings == []
    assert any("STEP marker fsynced" in v for v in verdicts)


def test_e1223_rename_needs_preceding_fsync():
    bad = (
        "import os\n"
        "def torn(path, data):\n"
        "    tmp = path + '.tmp'\n"
        "    with open(tmp, 'wb') as f:\n"
        "        f.write(data)\n"
        "    os.replace(tmp, path)\n")
    findings, _ = _ordering(bad)
    assert [f.code for f in findings] == ["E1223"]
    good = bad.replace(
        "    os.replace(tmp, path)\n",
        "    os.fsync(3)\n    os.replace(tmp, path)\n")
    findings, verdicts = _ordering(good)
    assert findings == []
    assert any("fsync-before-rename holds" in v for v in verdicts)
    # outside the durable scopes the rule does not apply (generator
    # outputs are fenced by the INCOMPLETE-tag protocol instead)
    findings, _ = _ordering(bad, fsync_scope=False)
    assert findings == []


# -- real-tree acceptance ---------------------------------------------------

def test_effects_real_tree_baseline_zero():
    """THE acceptance criterion: the repo proves every effect contract
    — commit-scope discipline, psum budget, write orderings — with
    nothing baselined (the one justified ``# noqa: E1223`` on
    ``atomic_replace_bytes`` is suppression-with-reason, not debt)."""
    findings = driver.run_passes(driver.Context(REPO),
                                 pass_names={"effects"})
    assert findings == [], "\n".join(f.render() for f in findings)


def test_effects_real_tree_proofs_nonvacuous():
    ctx = driver.Context(REPO)
    lines = effects_pass.verdict_report(ctx)
    text = "\n".join(lines)
    assert "[FAIL]" not in text
    # the three headline proofs of the acceptance criteria
    assert "manifest-written-last" in text
    assert "rewards_and_penalties budget=1" in text
    assert "0 escape a scope unguarded" in text
    assert "STEP marker fsynced" in text
    # non-vacuity: the closure really carries deferrable write sites
    # and the scope roots really exist
    analysis = ctx._effects_scope_memo
    assert len(analysis.scopes) >= 2
    assert sum(len(ev.writes) for ev in analysis._events.values()) > 0
    # increase_balance's own summary is an unguarded write — only the
    # guarded call edges keep it out of the scopes
    inc = analysis.graph.classes["Phase0Spec"].methods["increase_balance"]
    assert any(f[0] == "uwrite" for f in analysis._summaries[inc])


def test_effect_verdicts_cli(capsys):
    assert driver.main([REPO, "--effect-verdicts"]) == 0
    out = capsys.readouterr().out
    assert "PSUM" in out.upper() or "psum" in out
    assert "[PROVEN]" in out


# -- dependency-granular cache + --changed + warm budget --------------------

def test_input_shas_for_scopes_tree_passes():
    from consensus_specs_tpu.tools.speclint.passes import (
        coverage as cov_pass, determinism as det_pass)
    ctx = driver.Context(REPO)
    eff_files = {r for r, _ in ctx.input_shas_for(effects_pass)}
    cov_files = {r for r, _ in ctx.input_shas_for(cov_pass)}
    det_files = {r for r, _ in ctx.input_shas_for(det_pass)}
    assert not any(r.startswith("tests/") for r in eff_files)
    assert not any(r.startswith("consensus_specs_tpu/tools/")
                   for r in eff_files | det_files | cov_files)
    assert any(r.startswith("tests/") for r in cov_files)
    assert "Makefile" in cov_files
    # passes without the declaration keep the whole tree
    class _Plain:
        pass
    assert {r for r, _ in ctx.input_shas_for(_Plain)} \
        == {r for r, _ in ctx.input_shas()}


def test_tree_cache_dependency_granularity(tmp_path):
    """Editing a tests/ file re-runs ONLY the coverage pass; the other
    tree passes (ladder, determinism, effects) stay warm."""
    root = tmp_path / "repo"
    _write(root, SCOPED, "def f(seq):\n    return u64_column(seq)\n")
    _write(root, "tests/test_probe.py", "def test_ok():\n    pass\n")
    assert driver.main([str(root)]) == 0
    _write(root, "tests/test_probe.py", "def test_ok():\n    assert 1\n")
    ctx = driver.Context(str(root))
    cache = sl_cache.AnalysisCache(
        os.path.join(str(root), sl_cache.CACHE_NAME), driver._pass_salt())
    driver.run_passes(ctx, cache=cache)
    assert cache.stats["tree_misses"] == 1     # coverage only
    assert cache.stats["tree_hits"] == 4       # ladder/determinism/effects/cost


def test_warm_lint_time_budget(tmp_path):
    """The satellite bound: a warm full lint of the REAL tree serves
    everything from the cache inside the asserted budget."""
    cache_path = str(tmp_path / "cache.json")
    ctx = driver.Context(REPO)
    cache = sl_cache.AnalysisCache(cache_path, driver._pass_salt())
    driver.run_passes(ctx, cache=cache)
    cache.save()
    ctx2 = driver.Context(REPO)
    cache2 = sl_cache.AnalysisCache(cache_path, driver._pass_salt())
    t0 = time.perf_counter()
    driver.run_passes(ctx2, cache=cache2)
    took = time.perf_counter() - t0
    assert cache2.stats["file_misses"] == 0
    assert cache2.stats["tree_misses"] == 0
    assert took < 5.0, f"warm lint took {took:.2f}s (budget 5s)"


def test_changed_mode_lints_only_dirty(tmp_path, capsys):
    if shutil.which("git") is None:
        import pytest
        pytest.skip("git unavailable")
    root = tmp_path / "repo"
    dirty_src = ("def f(seq):\n"
                 "    b = u64_column(seq)\n"
                 "    p = u64_column(seq)\n"
                 "    return b - p\n")
    _write(root, SCOPED, "def f(seq):\n    return u64_column(seq)\n")
    _write(root, "consensus_specs_tpu/utils/other.py", dirty_src)
    for cmd in (["git", "init", "-q"],
                ["git", "config", "user.email", "t@t"],
                ["git", "config", "user.name", "t"],
                ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=str(root), check=True)
    # dirty exactly one file with a fresh finding
    _write(root, SCOPED, dirty_src)
    rc = driver.main([str(root), "--changed", "--no-baseline",
                      "--no-incremental"])
    out = capsys.readouterr().out
    assert rc == 1
    assert SCOPED in out
    # the committed-but-unchanged file's identical finding is NOT
    # reported: --changed restricted the file-pass candidates
    assert "consensus_specs_tpu/utils/other.py" not in out


def test_durability_covers_compiler_tree():
    """The real E12xx-era finding: the spec compiler's module/manifest
    writes were bare final-path opens — R901's scope now guards the
    compiler tree so the torn-write idiom cannot come back."""
    assert durability.in_scope("consensus_specs_tpu/compiler/emit.py")
    src = ("def emit(path, src):\n"
           "    with open(path, 'w') as f:\n"
           "        f.write(src)\n")
    findings = durability.check_source(
        "consensus_specs_tpu/compiler/emit.py", src)
    assert [f.code for f in findings] == ["R901"]


# ---------------------------------------------------------------------------
# review regressions (E12xx)
# ---------------------------------------------------------------------------

def test_e1202_finding_anchors_in_defining_file(tmp_path):
    """Review regression: a fork/checkpoint fact escaping to a scope in
    ANOTHER file must anchor at its own call site, not at an arbitrary
    line of the scope root's file (noqa matching is path+line)."""
    engine = _FX_ENGINE_GUARDED.replace(
        "from consensus_specs_tpu.state import arrays as state_arrays\n",
        "from consensus_specs_tpu.state import arrays as state_arrays\n"
        "from consensus_specs_tpu.ops.helper import deep_fork\n").replace(
        "    def process_epoch(self, state):\n",
        "    def process_epoch(self, state):\n"
        "        deep_fork(state)\n")
    helper = (
        "from consensus_specs_tpu.state import arrays as state_arrays\n"
        "def deep_fork(state):\n"
        "    return state_arrays.fork_state(state)\n")
    findings = effects_pass.check_tree(_fx_tree(
        tmp_path, engine=engine,
        extra=[("consensus_specs_tpu/ops/helper.py", helper)]))
    assert [f.code for f in findings] == ["E1202"]
    assert findings[0].path == "consensus_specs_tpu/ops/helper.py"
    assert findings[0].line == 3


def test_changed_mode_sees_untracked_directories(tmp_path, capsys):
    """Review regression: `git status --porcelain` collapses a new
    directory to one `?? dir/` entry; --changed must still lint the
    files inside it (--untracked-files=all)."""
    if shutil.which("git") is None:
        import pytest
        pytest.skip("git unavailable")
    root = tmp_path / "repo"
    _write(root, "consensus_specs_tpu/utils/seed.py", "x = 1\n")
    for cmd in (["git", "init", "-q"],
                ["git", "config", "user.email", "t@t"],
                ["git", "config", "user.name", "t"],
                ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=str(root), check=True)
    # a brand-new untracked DIRECTORY containing a finding (under a
    # uint64-pass-scoped prefix)
    _write(root, "consensus_specs_tpu/parallel/newpkg/kernels.py",
           "def f(seq):\n"
           "    b = u64_column(seq)\n"
           "    p = u64_column(seq)\n"
           "    return b - p\n")
    rc = driver.main([str(root), "--changed", "--no-baseline",
                      "--no-incremental"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "consensus_specs_tpu/parallel/newpkg/kernels.py" in out


# ---------------------------------------------------------------------------
# N13xx cost pass: asymptotic host-work proofs over the registry axis
# ---------------------------------------------------------------------------

from consensus_specs_tpu.tools.speclint.passes import cost as cost_pass

_CX_ON_BODY = (
    "        cols = sa.registry()\n"
    "        eff = cols['eff']\n"
    "        total = eff.sum()\n"
    "        return True\n")

_CX_OS_BODY = (
    "        parts = np.asarray(_p_stats(None)(sa.registry()))\n"
    "        total = parts.max()\n"
    "        return True\n")

_CX_ENGINE = (
    "import numpy as np\n"
    "def _dispatch(spec, state, sub, fast):\n"
    "    return fast(spec, state, None)\n"
    "def _p_stats(mesh):\n"
    "    def build():\n"
    "        def local(eff):\n"
    "            return eff\n"
    "        return local\n"
    "    return build()\n"
    "def try_demo(spec, state):\n"
    "    def fast(spec, state, sa):\n"
    "{body}"
    "    return _dispatch(spec, state, 'demo', fast)\n")

_CX_REL = "consensus_specs_tpu/parallel/demo_engine.py"


def _cx_tree(tmp_path, body=_CX_ON_BODY, prefix="", rel=_CX_REL):
    root = tmp_path / "repo"
    _write(root, rel, prefix + _CX_ENGINE.format(body=body))
    return str(root)


def test_n1301_column_reduce_in_dispatch_path(tmp_path):
    findings = cost_pass.check_tree(_cx_tree(tmp_path))
    assert "N1301" in _codes(findings)
    (f,) = [f for f in findings if f.code == "N1301"]
    assert f.path == _CX_REL
    verdicts = cost_pass.analysis_for(_cx_tree(tmp_path)).verdicts()
    assert any("[FAIL]" in v and "demo" in v for v in verdicts)


def test_n1301_partial_reduce_is_proven(tmp_path):
    root = _cx_tree(tmp_path, body=_CX_OS_BODY)
    assert _codes(cost_pass.check_tree(root)) == []
    verdicts = cost_pass.analysis_for(root).verdicts()
    assert any("[PROVEN]" in v and "O(S)" in v for v in verdicts)


def test_n1301_noqa_suppresses_and_counts(tmp_path):
    body = _CX_ON_BODY.replace("eff.sum()", "eff.sum()  # noqa: N1301")
    root = _cx_tree(tmp_path, body=body)
    assert _codes(cost_pass.check_tree(root)) == []
    verdicts = cost_pass.analysis_for(root).verdicts()
    assert any("[PROVEN]" in v and "suppressed" in v for v in verdicts)


def test_n1301_interprocedural_through_function_arg(tmp_path):
    # the _supervised(..., fast_fn) convention: the O(n) body is only
    # reachable through a function REFERENCE passed as an argument
    body = "        return _run(spec, state, _worker)\n"
    prefix = (
        "def _worker(spec, state, sa):\n"
        "    eff = u64_column(state)\n"
        "    return int(eff.sum())\n"
        "def _run(spec, state, fn):\n"
        "    return fn(spec, state, None)\n")
    findings = cost_pass.check_tree(
        _cx_tree(tmp_path, body=body, prefix=prefix))
    assert "N1301" in _codes(findings)
    (f,) = [f for f in findings if f.code == "N1301"]
    assert "_worker" in f.message


def test_n1301_audit_branch_is_exempt(tmp_path):
    body = (
        "        if supervisor.audit_due('demo'):\n"
        "            g = sa.registry()['eff'].sum()\n"
        "        return True\n")
    assert _codes(cost_pass.check_tree(_cx_tree(tmp_path, body=body))) \
        == []


def test_n1302_gather_only_column_derivation(tmp_path):
    body = (
        "        eff = sa.registry()['eff']\n"
        "        base = eff * np.uint64(64)\n"
        "        src_idx = np.nonzero(state.flags)[0]\n"
        "        out = base[src_idx]\n"
        "        return True\n")
    codes = _codes(cost_pass.check_tree(_cx_tree(tmp_path, body=body)))
    assert "N1302" in codes


def test_n1303_unbounded_cache_and_bounded_annotation(tmp_path):
    body = (
        "        _CACHE[(id(spec), id(state))] = 1\n"
        "        return True\n")
    prefix = "_CACHE = {}\n"
    codes = _codes(cost_pass.check_tree(
        _cx_tree(tmp_path, body=body, prefix=prefix)))
    assert "N1303" in codes
    bounded = "# speclint: cost: bounded: one probe pair\n" + prefix
    assert "N1303" not in _codes(cost_pass.check_tree(
        _cx_tree(tmp_path, body=body, prefix=bounded)))


def test_n1303_evicted_cache_is_clean(tmp_path):
    body = (
        "        _CACHE.pop(None, None)\n"
        "        _CACHE[(id(spec), id(state))] = 1\n"
        "        return True\n")
    assert "N1303" not in _codes(cost_pass.check_tree(
        _cx_tree(tmp_path, body=body, prefix="_CACHE = {}\n")))


def test_n1304_checked_annotations(tmp_path):
    # an O(1) claim on an O(n) path fails; an honest O(n) claim and a
    # matching O(S) claim both verify; a malformed bound is reported
    over = _cx_tree(tmp_path, prefix="")
    src = open(os.path.join(over, _CX_REL)).read()
    with open(os.path.join(over, _CX_REL), "w") as f:
        f.write(src.replace("def try_demo(spec, state):\n",
                            "# speclint: cost: O(1)\n"
                            "def try_demo(spec, state):\n"))
    findings = cost_pass.check_tree(over)
    assert any(f.code == "N1304" and "O(n)" in f.message
               for f in findings)
    with open(os.path.join(over, _CX_REL), "w") as f:
        f.write(src.replace("def try_demo(spec, state):\n",
                            "# speclint: cost: O(n)\n"
                            "def try_demo(spec, state):\n"))
    assert "N1304" not in _codes(cost_pass.check_tree(over))
    with open(os.path.join(over, _CX_REL), "w") as f:
        f.write(src.replace("def try_demo(spec, state):\n",
                            "# speclint: cost: O(n^2)\n"
                            "def try_demo(spec, state):\n"))
    assert any(f.code == "N1304" and "unparseable" in f.message
               for f in findings + cost_pass.check_tree(over))


def test_cost_real_tree_baseline_zero():
    """Acceptance: the REAL tree carries zero unsuppressed N13xx debt
    (the baseline records none), and every dispatch path proves O(S)."""
    assert cost_pass.check_tree(REPO) == []
    verdicts = cost_pass.analysis_for(REPO).verdicts()
    assert len(verdicts) >= 5
    assert all("[PROVEN]" in v for v in verdicts)
    assert not any("[FAIL]" in v for v in verdicts)


def test_cost_real_tree_proofs_nonvacuous():
    """The proofs must be doing work on the real tree: the shard
    programs pin at O(n/S), and at least one dispatch path reduces a
    per-shard partial stack (an O(S) fact on parallel/)."""
    from consensus_specs_tpu.tools.speclint import cost as cost_core
    a = cost_pass.analysis_for(REPO)
    assert any(total == cost_core.ONS
               for total, _ in a.summaries.values())
    os_facts = 0
    for fn in a.reachable():
        if fn in a._pinned or not fn.rel.startswith(
                "consensus_specs_tpu/parallel/"):
            continue
        for _, rank, reportable, _ in a._local(fn).facts:
            if reportable and rank == cost_core.OS:
                os_facts += 1
    assert os_facts >= 1


def test_cost_verdicts_cli(capsys):
    assert driver.main([REPO, "--cost-verdicts"]) == 0
    out = capsys.readouterr().out
    assert "host-work budget" in out
    assert "[PROVEN]" in out and "[FAIL]" not in out


# ---------------------------------------------------------------------------
# SARIF baselineState: "absent" (fixed baseline debt)
# ---------------------------------------------------------------------------

def test_sarif_absent_for_stale_baseline_keys():
    log = sarif.to_sarif([], [], stale=["consensus_specs_tpu/x.py::U101"])
    assert sarif.validate(log) == []
    (result,) = log["runs"][0]["results"]
    assert result["baselineState"] == "absent"
    assert result["ruleId"] == "U101"
    assert result["level"] == "none"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "consensus_specs_tpu/x.py"
    assert loc["region"]["startLine"] == 1
    rule_ids = {r["id"] for r in log["runs"][0]["tool"]["driver"]["rules"]}
    assert "U101" in rule_ids


def test_sarif_driver_emits_absent_for_fixed_debt(tmp_path, capsys):
    """End-to-end: a baseline entry whose finding is gone surfaces as a
    schema-valid `absent` result in `--format sarif`."""
    root = tmp_path / "repo"
    _write(root, SCOPED, "def f(seq):\n    return u64_column(seq)\n")
    _write(root, driver.BASELINE_NAME, json.dumps(
        {"counts": {SCOPED + "::U101": 1}}))
    rc = driver.main([str(root), "--passes", "uint64",
                      "--format", "sarif"])
    assert rc == 0
    log = json.loads(capsys.readouterr().out)
    assert sarif.validate(log) == []
    states = [r["baselineState"] for r in log["runs"][0]["results"]]
    assert states == ["absent"]


# ---------------------------------------------------------------------------
# --changed vs renamed / deleted dirty files
# ---------------------------------------------------------------------------

def test_changed_mode_purges_renamed_and_deleted(tmp_path, capsys):
    """Review regression: a dirty rename (R old -> new) or delete (D)
    must purge the OLD path's cached findings — a stale cache entry
    would otherwise resurrect findings for a file that no longer
    exists."""
    if shutil.which("git") is None:
        import pytest
        pytest.skip("git unavailable")
    root = tmp_path / "repo"
    buggy = ("def f(seq):\n"
             "    b = u64_column(seq)\n"
             "    p = u64_column(seq)\n"
             "    return b - p\n")
    old_rel = "consensus_specs_tpu/parallel/old_kernels.py"
    dead_rel = "consensus_specs_tpu/parallel/dead_kernels.py"
    new_rel = "consensus_specs_tpu/parallel/new_kernels.py"
    _write(root, old_rel, buggy)
    _write(root, dead_rel, buggy)
    for cmd in (["git", "init", "-q"],
                ["git", "config", "user.email", "t@t"],
                ["git", "config", "user.name", "t"],
                ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=str(root), check=True)
    # warm the cache with both files' findings
    assert driver.main([str(root), "--no-baseline"]) == 1
    capsys.readouterr()
    cache_path = os.path.join(str(root), sl_cache.CACHE_NAME)
    cache = sl_cache.AnalysisCache(cache_path, driver._pass_salt())
    assert old_rel in cache._data["files"]
    assert dead_rel in cache._data["files"]
    # dirty: rename one file (staged, R entry), delete the other
    subprocess.run(["git", "mv", old_rel, new_rel], cwd=str(root),
                   check=True)
    os.remove(os.path.join(str(root), dead_rel))
    rc = driver.main([str(root), "--changed", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert new_rel in out
    assert old_rel not in out and dead_rel not in out
    cache = sl_cache.AnalysisCache(cache_path, driver._pass_salt())
    assert old_rel not in cache._data["files"]
    assert dead_rel not in cache._data["files"]
    assert new_rel in cache._data["files"]
