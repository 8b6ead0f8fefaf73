"""Effect & concurrency analysis core (the E12xx pass family's engine).

Three analyses share this module, each turning a contract the runtime
layers (PR 7/9/12/14) enforce dynamically — counters, sentinel audits,
fail-loud generation checks — into a machine-checked *static proof*:

1. **Commit-scope effect proofs** (:class:`CommitScopeAnalysis`).
   Per-function read/write *effect summaries* over the StateArrays
   deferrable column families (``balances``, ``inactivity_scores``) and
   their SSZ field paths, solved to a fixed point over the project call
   graph (``dataflow.solve``) with *virtual dispatch*: ``self.m`` calls
   union over every subclass override, so the closure of a
   ``with arrays.commit_scope(state):`` body covers the whole fork
   ladder the way runtime dispatch does.  A direct SSZ write to a
   deferrable column is *guarded* when a store flush
   (``state_arrays.flush`` / ``StateArrays.commit``) precedes it — own
   or through a transitively-flushing callee — in source order; an
   unguarded write escaping to a commit-scope root is the exact class
   ``StateArrays._cell``/``commit`` fail loud on at runtime (E1201).
   ``fork_state`` (E1202) and checkpoint saves (E1203) escaping to a
   scope root are the classes ``fork``-commits-early and
   ``CheckpointRefused`` only catch dynamically.

   The epoch bodies carry no dispatch call of their own:
   ``install_vectorized_epoch`` wraps each fork class's definition so
   the ``try_process_*`` dispatch (which flushes the store on a
   decline) runs before the body.  The analysis models that wrapper
   from its ``_TRY_BY_NAME`` table: a ``register_fork`` class's method
   named there is entered through the table's dispatch function.

   Classes that opt out of deferred commits
   (``_defer_epoch_commits = False``, e.g. custody_game) are excluded
   from the scope closure — their epoch bodies never run under an open
   scope, exactly as at runtime.

   The guard analysis is deliberately *under*-approximate in one
   direction (a flush anywhere earlier in source order counts, even
   inside a branch): zero false positives is the design point, and the
   ``CS_TPU_SANITIZER`` runtime twin (``consensus_specs_tpu/
   sanitizer.py``) arms the same contracts dynamically for the paths
   the linearization cannot see.

2. **Shard-safety race detection** (:func:`analyze_shard_module`).
   Every ``shard_map`` program body in ``parallel/`` is located from
   the AST (the builder convention: a nested ``local`` def handed to
   ``shard_map``), closed over its module-local helpers, and checked
   for the SPMD hygiene rules: no captured live host state (E1211 —
   a device body reading ``sa``/``spec``/``state`` mid-program is a
   cross-shard race outside the declared collective points), no host
   concretization (E1212 — ``int()``/``.item()``/``np.*`` inside a
   traced body), and the ``PSUM_BUDGET`` census (E1214): the psum
   count of every reducing program, and the per-sub-transition sum of
   psums over the programs each dispatch body calls, must equal the
   module's declared budget — the same invariant the runtime
   ``mesh.psums`` counters and the jaxpr census in ``tests/test_mesh``
   assert, proven here before any device exists.  E1213 (separately,
   over the engine consumers) flags in-place mutation of the read-only
   store accessors' returns — a write that does not retire the cached
   ``_Cell.shard`` placement because it never creates a fresh array
   identity.

3. **Happens-before write-ordering verification**
   (:func:`analyze_ordering`) — R901's generalization from per-call
   syntax to *ordered effect sequences* over the recovery surfaces:
   every checkpoint blob write must precede the manifest write and the
   manifest must be the function's last persistence effect (E1221,
   manifest-written-last); journal event records must precede their
   STEP commit marker and the marker's writer must fsync after the
   write (E1222); a final-path rename must be preceded by an fsync of
   the data in the same function (E1223 — ``atomic_replace_bytes``
   carries a justified ``# noqa``: its fencing is the generator's
   INCOMPLETE-tag protocol).

Positive proofs are printable via ``speclint --effect-verdicts``.
"""
import ast
import builtins

from .astutil import is_generated
from .dataflow import solve
from .findings import Finding
from .graph import ModuleGraph

ARRAYS_REL = "consensus_specs_tpu/state/arrays.py"
CHECKPOINT_REL = "consensus_specs_tpu/recovery/checkpoint.py"
# the enforcement layers themselves: the store's committer and the
# runtime sanitizer legitimately touch the SSZ lists they guard
ENFORCEMENT_RELS = (ARRAYS_REL, "consensus_specs_tpu/sanitizer.py")

# SSZ field names of the column families whose engine writes may sit
# deferred in the store across an open commit scope (state/arrays.py
# _DEFERRABLE) — a direct write to these fields is the hazard
DEFERRABLE_FIELDS = ("balances", "inactivity_scores")

OPT_OUT_ATTR = "_defer_epoch_commits"

# install_vectorized_epoch's method -> try_process_* dispatch table,
# and the decorator whose classes the installs wrap
EPOCH_KERNELS_REL = "consensus_specs_tpu/ops/epoch_kernels.py"
DISPATCH_TABLE = "_TRY_BY_NAME"
INSTALLING_DECORATOR = "register_fork"


def _tail(call):
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _owner(call):
    f = call.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        return f.value.id
    return None


def _pos(node):
    return (node.lineno, node.col_offset)


# ---------------------------------------------------------------------------
# 1. Commit-scope effect proofs (E1201/E1202/E1203)
# ---------------------------------------------------------------------------

class _FnEvents:
    """One function's ordered local effects: deferrable SSZ writes,
    store flushes, fork_state / checkpoint calls, and resolved call
    sites (for interprocedural propagation)."""

    __slots__ = ("writes", "flush_lines", "forks", "checkpoints", "calls")

    def __init__(self):
        self.writes = []        # (pos, fam, lineno)
        self.flush_lines = []   # (pos,)
        self.forks = []         # (pos, (rel, lineno))
        self.checkpoints = []   # (pos, (rel, lineno))
        self.calls = []         # (pos, frozenset(targets))


class CommitScopeAnalysis:
    """Whole-ladder commit-scope discipline prover (module docstring)."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.graph = ctx.project_graph()
        self._subclasses = {}     # class name -> class names with it in MRO
        for name in self.graph.classes:
            for base in self.graph.mro(name):
                self._subclasses.setdefault(base.name, set()).add(name)
        self.opted_out = self._opted_out_classes()
        self._dispatch = self._dispatch_entries()
        # the analyzed universe: everything on the graph except code
        # that can only run on an opted-out class, the enforcement
        # layers themselves, and the AUTO-COMPILED ladder — its bodies
        # are verbatim markdown whose guard is the runtime
        # install-wrapper (try_ before orig), so the proof defers to
        # the hand twin exactly as the determinism pass does (the L3xx
        # ladder pass pins hand/compiled surface parity)
        generated = {rel for rel in self.graph.modules
                     if is_generated(ctx.source(rel))}
        self.fns = [fn for fn in self.graph.functions
                    if fn.cls_name not in self.opted_out
                    and not fn.rel.startswith(ENFORCEMENT_RELS)
                    and fn.rel not in generated]
        self._fn_set = set(self.fns)
        self._events = {fn: self._extract(fn) for fn in self.fns}
        self._flushes = self._solve_flushes()
        self._summaries = self._solve_escapes()
        self.scopes = self._find_scopes()

    # -- class model --------------------------------------------------------

    def _opted_out_classes(self):
        """Classes whose MRO-resolved ``_defer_epoch_commits`` is False:
        their epoch bodies never run under an open commit scope."""
        out = set()
        for name in self.graph.classes:
            for cls in self.graph.mro(name):
                val = _class_attr(cls.node, OPT_OUT_ATTR)
                if val is not None:
                    if val is False:
                        out.add(name)
                    break
        return out

    def _dispatch_entries(self):
        """``(class name, method name) -> try_process_*`` for every
        method ``install_vectorized_epoch`` wraps: the keys of its
        ``_TRY_BY_NAME`` table, defined on a ``register_fork`` class."""
        mod = self.graph.modules.get(EPOCH_KERNELS_REL)
        if mod is None:
            return {}
        table = {}
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == DISPATCH_TABLE \
                    and isinstance(node.value, ast.Dict):
                for key, val in zip(node.value.keys, node.value.values):
                    if isinstance(key, ast.Constant) \
                            and isinstance(val, ast.Name) \
                            and val.id in mod.funcs:
                        table[key.value] = mod.funcs[val.id]
        out = {}
        for cls in self.graph.classes.values():
            if not any(_tail(d) == INSTALLING_DECORATOR
                       for d in cls.node.decorator_list
                       if isinstance(d, ast.Call)):
                continue
            for meth in cls.methods:
                if meth in table:
                    out[(cls.name, meth)] = table[meth]
        return out

    # -- resolution (virtual dispatch) --------------------------------------

    def _resolve(self, fn, call):
        """Graph resolution plus subclass-override union for ``self.m``
        calls and a method-name union for the store/checkpoint verbs
        the graph cannot see through an instance variable."""
        targets = set(self.graph.resolve_call(fn, call))
        f = call.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            base, meth = f.value.id, f.attr
            if base in ("self", "cls") and fn.cls_name:
                for sub in self._subclasses.get(fn.cls_name, ()):
                    got = self.graph.resolve_method(sub, meth)
                    if got is not None:
                        targets.add(got)
            elif not targets and meth in ("save", "commit", "flush"):
                # instance-variable dispatch (store.save(...),
                # sa.commit()): union over every class defining the
                # method — over-approximate toward reporting
                for cls in self.graph.classes.values():
                    if meth in cls.methods:
                        targets.add(cls.methods[meth])
        return {t for t in targets if t.cls_name not in self.opted_out}

    def _is_flush_target(self, t):
        if t.rel == ARRAYS_REL and t.name in ("flush", "commit"):
            return True
        return False

    def _is_fork_target(self, t):
        return t.rel == ARRAYS_REL and t.name == "fork_state"

    def _is_checkpoint_target(self, t):
        return t.rel == CHECKPOINT_REL and t.name in ("save",
                                                      "_write_generation")

    # -- local extraction ---------------------------------------------------

    def _extract_into(self, fn, nodes, ev):
        for node in nodes:
            fam = _deferrable_write(node)
            if fam is not None:
                ev.writes.append((_pos(node), fam, node.lineno))
            if not isinstance(node, ast.Call):
                continue
            targets = self._resolve(fn, node)
            flushed = any(self._is_flush_target(t) for t in targets)
            if flushed:
                ev.flush_lines.append(_pos(node))
            for t in targets:
                if self._is_fork_target(t):
                    # the fact carries its DEFINING site (rel, lineno):
                    # the finding must anchor at the call, not at
                    # whatever scope root it escapes to
                    ev.forks.append((_pos(node), (fn.rel, node.lineno)))
                if self._is_checkpoint_target(t):
                    ev.checkpoints.append(
                        (_pos(node), (fn.rel, node.lineno)))
            inner = {t for t in targets if t in self._fn_set}
            if inner:
                ev.calls.append((_pos(node), frozenset(inner)))

    def _extract(self, fn):
        ev = _FnEvents()
        entry = self._dispatch.get((fn.cls_name, fn.name))
        if entry is not None and entry in self._fn_set:
            # the installed wrapper's dispatch runs before the body
            ev.calls.append(((fn.node.lineno, -1), frozenset({entry})))
        self._extract_into(fn, ast.walk(fn.node), ev)
        return ev

    # -- fixed points --------------------------------------------------------

    def _solve_flushes(self):
        """Phase 1 (monotone): which functions may flush the store,
        directly or transitively."""
        events = self._events

        def callees_of(fn):
            out = set()
            for _, targets in events[fn].calls:
                out |= targets
            return out

        def transfer(fn, get):
            if events[fn].flush_lines:
                return True
            for _, targets in events[fn].calls:
                if any(get(t) for t in targets if t in self._fn_set):
                    return True
            return False

        got = solve(self.fns, callees_of, transfer)
        return {fn for fn, v in got.items() if v}

    def _scan(self, ev, get_summary):
        """The linear-order transfer shared by function summaries and
        scope bodies: facts escaping past the guard discipline."""
        timeline = []
        for pos, fam, lineno in ev.writes:
            timeline.append((pos, "write", (fam, lineno)))
        for pos in ev.flush_lines:
            timeline.append((pos, "flush", None))
        for pos, lineno in ev.forks:
            timeline.append((pos, "fork", lineno))
        for pos, lineno in ev.checkpoints:
            timeline.append((pos, "checkpoint", lineno))
        for pos, targets in ev.calls:
            timeline.append((pos, "call", targets))
        timeline.sort(key=lambda e: e[0])
        out = set()
        guarded = False
        for pos, kind, payload in timeline:
            if kind == "flush":
                guarded = True
            elif kind == "write":
                if not guarded:
                    fam, lineno = payload
                    # rel stamped by the caller (transfer / scope scan)
                    out.add(("uwrite", fam, None, lineno))
            elif kind == "fork":
                out.add(("fork", payload))
            elif kind == "checkpoint":
                out.add(("checkpoint", payload))
            elif kind == "call":
                for t in payload:
                    summary = get_summary(t)
                    if not summary:
                        continue
                    for fact in summary:
                        if fact[0] == "uwrite" and guarded:
                            continue
                        out.add(fact)
                if any(t in self._flushes for t in payload):
                    guarded = True
        return out

    def _solve_escapes(self):
        """Phase 2 (monotone once phase 1 is fixed): the facts escaping
        each function — unguarded deferrable writes (with their defining
        site), fork_state and checkpoint reachability."""
        events = self._events

        def callees_of(fn):
            out = set()
            for _, targets in events[fn].calls:
                out |= targets
            return out

        def transfer(fn, get):
            raw = self._scan(events[fn], lambda t: get(t) if t in
                             self._fn_set else None)
            # stamp this function's own unguarded writes with their site
            out = set()
            for fact in raw:
                if fact[0] == "uwrite" and fact[2] is None:
                    out.add(("uwrite", fact[1], fn.rel, fact[3]))
                else:
                    out.add(fact)
            return frozenset(out)

        return solve(self.fns, callees_of, transfer)

    # -- scope roots ---------------------------------------------------------

    def _find_scopes(self):
        """Every ``with ... commit_scope(...):`` statement in the
        analyzed universe, with the scope body's escaping facts."""
        scopes = []
        for fn in self.fns:
            for node in ast.walk(fn.node):
                if not isinstance(node, (ast.With, ast.AsyncWith)):
                    continue
                if not any(self._is_scope_item(fn, item)
                           for item in node.items):
                    continue
                ev = _FnEvents()
                body_nodes = [n for stmt in node.body
                              for n in ast.walk(stmt)]
                self._extract_into(fn, body_nodes, ev)
                self._wrap_orig_calls(body_nodes, ev)
                facts = self._scan(ev, self._summaries.get)
                facts = {("uwrite", f[1], fn.rel, f[3])
                         if f[0] == "uwrite" and f[2] is None else f
                         for f in facts}
                scopes.append((fn, node.lineno, facts))
        return scopes

    def _is_scope_item(self, fn, item):
        expr = item.context_expr
        if not isinstance(expr, ast.Call):
            return False
        if _tail(expr) != "commit_scope":
            return False
        targets = self.graph.resolve_call(fn, expr)
        # resolved to the real helper, or unresolvable-by-name (the
        # fixture trees may not carry a full arrays module)
        return not targets or any(t.rel == ARRAYS_REL for t in targets)

    def _wrap_orig_calls(self, body_nodes, ev):
        """``install_vectorized_epoch`` wraps every ``process_epoch``
        body through a ``_orig(self, state)`` cell — statically
        unresolvable, so the scope body unions every non-opted-out
        ``process_epoch`` definition (exactly what the wrapper wraps)."""
        for node in body_nodes:
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "_orig":
                targets = set()
                for cls in self.graph.classes.values():
                    if cls.name in self.opted_out:
                        continue
                    m = cls.methods.get("process_epoch")
                    if m is not None and m in self._fn_set:
                        targets.add(m)
                if targets:
                    ev.calls.append((_pos(node), frozenset(targets)))

    # -- reporting -----------------------------------------------------------

    def findings(self):
        out = []
        seen = set()
        for fn, scope_line, facts in self.scopes:
            where = f"{fn.rel}:{scope_line}"
            for fact in sorted(facts, key=repr):
                if fact[0] == "uwrite":
                    _, fam, rel, lineno = fact
                    key = ("E1201", rel, lineno)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(Finding(
                        rel, lineno, "E1201",
                        f"direct SSZ write to the deferrable {fam} "
                        f"column reachable inside the commit scope at "
                        f"{where} with no store flush before it — the "
                        "pending deferred column write would be "
                        "clobbered (the class StateArrays.commit fails "
                        "loud on at runtime); flush via "
                        "state_arrays.flush(state) first"))
                elif fact[0] == "fork":
                    rel, lineno = fact[1]
                    key = ("E1202", rel, lineno)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(Finding(
                        rel, lineno, "E1202",
                        f"fork_state reachable inside the commit scope "
                        f"at {where} — forking commits the pending "
                        "columns mid-scope, silently degrading the "
                        "one-commit-per-epoch contract"))
                elif fact[0] == "checkpoint":
                    rel, lineno = fact[1]
                    key = ("E1203", rel, lineno)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(Finding(
                        rel, lineno, "E1203",
                        f"checkpoint save reachable inside the commit "
                        f"scope at {where} — the state's SSZ bytes are "
                        "not authoritative mid-transition (the class "
                        "CheckpointRefused fails loud on at runtime)"))
        return out

    def verdicts(self):
        lines = []
        n_writes = sum(len(ev.writes) for ev in self._events.values())
        escaped = len({(f[2], f[3]) for _, _, facts in self.scopes
                       for f in facts if f[0] == "uwrite"})
        lines.append(
            f"commit-scope: {len(self.scopes)} scope root(s), "
            f"{len(self.fns)} functions analyzed, "
            f"{n_writes} direct deferrable-column write site(s), "
            f"{escaped} escape a scope unguarded")
        for fn, scope_line, facts in self.scopes:
            bad = sum(1 for f in facts if f[0] == "uwrite")
            forks = sum(1 for f in facts if f[0] == "fork")
            ckpts = sum(1 for f in facts if f[0] == "checkpoint")
            verdict = "PROVEN" if not (bad or forks or ckpts) else "FAIL"
            lines.append(
                f"  [{verdict}] scope {fn.rel}:{scope_line} "
                f"({fn.qname.split('::')[-1]}): "
                f"{bad} unguarded write(s), {forks} fork_state, "
                f"{ckpts} checkpoint call(s) escape")
        if self.opted_out:
            lines.append("  opted out of deferred commits "
                         f"({OPT_OUT_ATTR}=False): "
                         + ", ".join(sorted(self.opted_out)))
        return lines


def _class_attr(cls_node, attr):
    for node in cls_node.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == attr \
                        and isinstance(node.value, ast.Constant):
                    return node.value.value
    return None


def _deferrable_write(node):
    """The column family a statement writes directly through the SSZ
    API, if any: ``state.balances[i] = / += ...``, whole-field
    assignment, or ``state.balances.append(...)``."""
    target = None
    if isinstance(node, ast.Assign):
        if len(node.targets) == 1:
            target = node.targets[0]
    elif isinstance(node, ast.AugAssign):
        target = node.target
    if target is not None:
        if isinstance(target, ast.Subscript):
            target = target.value
        if isinstance(target, ast.Attribute) \
                and target.attr in DEFERRABLE_FIELDS:
            return target.attr
        return None
    if isinstance(node, ast.Call) and _tail(node) in ("append", "pop"):
        f = node.func
        if isinstance(f.value, ast.Attribute) \
                and f.value.attr in DEFERRABLE_FIELDS:
            return f.value.attr
    return None


# ---------------------------------------------------------------------------
# 2. Shard-safety race detection (E1211/E1212/E1214)
# ---------------------------------------------------------------------------

# names whose capture into a device program body is live host state
_LIVE_PARAM_NAMES = {"state", "sa", "spec", "store", "self", "cols",
                     "balances", "scores", "cell"}
# roots whose attribute-call results are live host state when bound in
# an enclosing scope (``cols = sa.registry()``)
_LIVE_ROOTS = {"state", "sa", "spec", "store", "self"}
_CONCRETIZE_NAMES = {"int", "float", "bool"}
_CONCRETIZE_TAILS = {"item", "tolist", "device_get", "block_until_ready"}
_COLLECTIVES = {"psum", "pmax", "pmin", "all_gather", "psum_scatter",
                "ppermute", "axis_index"}


class ShardProgram:
    """One ``shard_map`` program: the body def, its module-local
    closure, and the psum census."""

    __slots__ = ("builder", "body", "closure", "psums", "rel")

    def __init__(self, rel, builder, body, closure):
        self.rel = rel
        self.builder = builder      # enclosing top-level builder name
        self.body = body
        self.closure = closure
        self.psums = sum(
            1 for fn in closure for n in ast.walk(fn)
            if isinstance(n, ast.Call) and _tail(n) == "psum")


def _top_level_owner(mg, node):
    """The outermost enclosing function of a nested def."""
    while node in mg.parents:
        node = mg.parents[node]
    return node


def find_shard_programs(rel, tree):
    """Every function handed to ``shard_map`` in the module, closed
    over module-local helpers.  The body name is resolved LEXICALLY —
    every builder defines a nested ``local``, so the module-wide
    name map would alias them all onto one node."""
    mg = ModuleGraph(tree)
    # def node -> the function whose own body contains it (lexical)
    by_name = {}            # name -> [def nodes]
    for fn in set(mg.funcs.values()) | set(mg.parents):
        by_name.setdefault(fn.name, []).append(fn)
    programs = []
    seen = set()
    all_defs = list(set(mg.funcs.values()) | set(mg.parents))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or _tail(node) != "shard_map":
            continue
        if not node.args or not isinstance(node.args[0], ast.Name):
            continue
        name = node.args[0].id
        # the def visible from the call site: nearest enclosing scope
        # that lexically owns a def of that name
        enclosing = [fn for fn in all_defs if _directly_owns(fn, node)]
        body = None
        scope = enclosing[0] if enclosing else None
        while scope is not None and body is None:
            for cand in by_name.get(name, ()):
                if mg.parents.get(cand) is scope:
                    body = cand
                    break
            scope = mg.parents.get(scope)
        if body is None and len(by_name.get(name, ())) == 1:
            body = by_name[name][0]     # unique module-level def
        if body is None or id(body) in seen:
            continue
        seen.add(id(body))
        closure = mg.closure([body])
        owner = _top_level_owner(mg, body)
        programs.append(ShardProgram(rel, owner.name, body, closure))
    return programs


def _scope_bindings(fn_node):
    """Names bound inside one function scope (params, assignments,
    imports, nested defs) — NOT descending into nested functions."""
    bound = set()
    a = fn_node.args
    for arg in a.posonlyargs + a.args + a.kwonlyargs:
        bound.add(arg.arg)
    if a.vararg:
        bound.add(a.vararg.arg)
    if a.kwarg:
        bound.add(a.kwarg.arg)

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                bound.add(child.name)
                continue        # separate scope
            if isinstance(child, ast.Name) \
                    and isinstance(child.ctx, (ast.Store,)):
                bound.add(child.id)
            elif isinstance(child, (ast.Import, ast.ImportFrom)):
                for alias in child.names:
                    bound.add((alias.asname
                               or alias.name.split(".")[0]))
            visit(child)
    visit(fn_node)
    return bound


def _live_binding(expr):
    """True when a binding's value expression reads live host state:
    an attribute chain or call rooted at a live name
    (``sa.registry()``, ``state.balances``, ``spec.foo(...)``)."""
    node = expr
    while True:
        if isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        else:
            break
    return isinstance(node, ast.Name) and node.id in _LIVE_ROOTS


def _analyze_program(mg, module_names, prog):
    """E1211/E1212 findings for one program body closure."""
    findings = []
    # enclosing scope chain: nearest-first
    chain = []
    node = prog.body
    while node in mg.parents:
        node = mg.parents[node]
        chain.append(node)
    enclosing = []
    for fn in chain:
        params = {a.arg for a in fn.args.posonlyargs + fn.args.args
                  + fn.args.kwonlyargs}
        assigns = {}
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                assigns[n.targets[0].id] = n.value
        enclosing.append((fn, params, assigns, _scope_bindings(fn)))

    for fn in prog.closure:
        bound = _scope_bindings(fn)
        for n in ast.walk(fn):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                name = n.id
                if name in bound or name in module_names \
                        or hasattr(builtins, name):
                    continue
                # a free variable: captured from an enclosing scope
                live = False
                for _efn, params, assigns, ebound in enclosing:
                    if name not in ebound:
                        continue
                    if name in params:
                        live = name in _LIVE_PARAM_NAMES
                    elif name in assigns:
                        live = _live_binding(assigns[name])
                    break
                if live:
                    findings.append(Finding(
                        prog.rel, n.lineno, "E1211",
                        f"shard_map program body (builder "
                        f"{prog.builder}) reads captured host state "
                        f"{name!r} — a cross-shard state read outside "
                        "the declared collective points; pass it as a "
                        "sharded/replicated operand instead"))
            elif isinstance(n, ast.Call):
                tail = _tail(n)
                owner = _owner(n)
                if isinstance(n.func, ast.Name) \
                        and n.func.id in _CONCRETIZE_NAMES:
                    findings.append(Finding(
                        prog.rel, n.lineno, "E1212",
                        f"host concretization {n.func.id}() inside a "
                        f"shard_map program body (builder "
                        f"{prog.builder}) — forces a device sync "
                        "mid-program; compute on traced lanes or hoist "
                        "to the host dispatch"))
                elif tail in _CONCRETIZE_TAILS or owner == "np":
                    what = f"np.{tail}" if owner == "np" else f".{tail}()"
                    findings.append(Finding(
                        prog.rel, n.lineno, "E1212",
                        f"host concretization {what} inside a "
                        f"shard_map program body (builder "
                        f"{prog.builder}) — device code must stay on "
                        "traced lanes (jnp), not host numpy"))
    return findings


def _module_budget(tree):
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "PSUM_BUDGET" \
                and isinstance(node.value, ast.Dict):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) \
                        and isinstance(v, ast.Constant):
                    out[k.value] = v.value
            return out, node.lineno
    return None, None


def _dispatch_entries(tree):
    """``(fn_node, sub_name, lineno)`` for every function containing a
    ``_dispatch(..., "<sub>", ...)`` call."""
    mg = ModuleGraph(tree)
    out = []
    for fn in mg.funcs.values():
        for n in ast.walk(fn):
            if isinstance(n, ast.Call) and _tail(n) == "_dispatch":
                for arg in n.args:
                    if isinstance(arg, ast.Constant) \
                            and isinstance(arg.value, str):
                        out.append((fn, arg.value, n.lineno))
                        break
    # dedupe nested re-walks (ast.walk of an outer fn sees inner calls)
    seen = set()
    deduped = []
    for fn, sub, lineno in out:
        if (id(fn), sub, lineno) in seen:
            continue
        seen.add((id(fn), sub, lineno))
        deduped.append((fn, sub, lineno))
    return deduped, mg


def analyze_shard_module(rel, tree):
    """(findings, verdict lines) for one ``parallel/`` module."""
    findings = []
    verdicts = []
    programs = find_shard_programs(rel, tree)
    mg = ModuleGraph(tree)
    module_names = set(mg.funcs)
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                module_names.add(alias.asname
                                 or alias.name.split(".")[0])
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    module_names.add(t.id)
        elif isinstance(node, ast.ClassDef):
            module_names.add(node.name)
    for prog in programs:
        findings.extend(_analyze_program(mg, module_names, prog))
        if prog.psums > 1:
            findings.append(Finding(
                rel, prog.body.lineno, "E1214",
                f"shard_map program (builder {prog.builder}) contains "
                f"{prog.psums} psum calls — stack the partials and fold "
                "them through ONE psum per reducing program"))
    budget, budget_line = _module_budget(tree)
    if budget is None:
        if programs:
            verdicts.append(
                f"{rel}: {len(programs)} shard_map program(s), "
                f"{sum(p.psums for p in programs)} psum(s), "
                "no PSUM_BUDGET declared (non-reducing module)")
        return findings, verdicts

    by_builder = {}
    for prog in programs:
        by_builder[prog.builder] = \
            by_builder.get(prog.builder, 0) + prog.psums
    entries, mg2 = _dispatch_entries(tree)
    seen_subs = set()
    for entry_fn, sub, lineno in entries:
        if sub not in budget:
            findings.append(Finding(
                rel, lineno, "E1214",
                f"dispatched sub-transition {sub!r} has no PSUM_BUDGET "
                "entry — the collective budget cannot be proven"))
            continue
        seen_subs.add(sub)
        closure = mg2.closure([entry_fn])
        body_counts = {}
        for fn in closure:
            if fn.name in by_builder:
                continue        # the program builders themselves
            count = 0
            for n in ast.walk(fn):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Name) \
                        and n.func.id in by_builder:
                    # only this fn's own body: skip calls inside nested
                    # defs (they are separate closure entries)
                    if _directly_owns(fn, n):
                        count += by_builder[n.func.id]
            if count:
                body_counts[fn.name] = (count, fn.lineno)
        want = budget[sub]
        for name, (count, fline) in sorted(body_counts.items()):
            if count != want:
                findings.append(Finding(
                    rel, fline, "E1214",
                    f"dispatch body {name} runs {count} psum(s) for "
                    f"sub-transition {sub!r}; PSUM_BUDGET declares "
                    f"{want} — the collective census would diverge"))
        if want > 0 and not any(c == want
                                for c, _ in body_counts.values()):
            findings.append(Finding(
                rel, lineno, "E1214",
                f"sub-transition {sub!r} declares a psum budget of "
                f"{want} but no dispatch body runs a reducing program "
                "— the budget is unproven"))
        bodies = ", ".join(f"{n}={c}" for n, (c, _)
                           in sorted(body_counts.items())) or "none"
        ok = all(c == want for c, _ in body_counts.values()) \
            and (want == 0 or any(c == want
                                  for c, _ in body_counts.values()))
        verdicts.append(
            f"  [{'PROVEN' if ok else 'FAIL'}] {rel}: {sub} "
            f"budget={want} dispatch bodies: {bodies}")
    for sub in budget:
        if sub not in seen_subs:
            findings.append(Finding(
                rel, budget_line, "E1214",
                f"PSUM_BUDGET declares {sub!r} but no dispatch body "
                "carries that sub-transition — stale budget entry"))
    return findings, verdicts


def _directly_owns(fn, node):
    """True when ``node`` sits in ``fn``'s own body — the path from
    ``fn`` down to ``node`` crosses no nested function definition."""
    def search(owner):
        for child in ast.iter_child_nodes(owner):
            if child is node:
                return True
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if search(child):
                return True
        return False
    return search(fn)


# ---------------------------------------------------------------------------
# 2b. Placement-retirement discipline (E1213, engine consumers)
# ---------------------------------------------------------------------------

_ACCESSOR_TAILS = {"registry", "balances", "inactivity_scores",
                   "participation"}
_CLEANERS = {"copy", "astype", "registry_writable"}


def _accessor_call(expr):
    """True when ``expr`` is a read-only store accessor call
    (``sa.balances()``, ``registry_of(state)``)."""
    if not isinstance(expr, ast.Call):
        return False
    tail = _tail(expr)
    if tail == "registry_of":
        return True
    return tail in _ACCESSOR_TAILS and isinstance(expr.func, ast.Attribute)


def check_placement_retirement(rel, tree):
    """E1213: in-place mutation of a read-only store accessor's return
    (directly or through a local view) — the write keeps the array
    identity, so a cached ``_Cell.shard`` device placement would keep
    serving stale data and copy-on-write forks would see the mutation
    through their shared base."""
    findings = []
    for unit in [n for n in ast.walk(tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        tainted = set()
        for node in ast.walk(unit):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                val = node.value
                if _accessor_call(val):
                    tainted.add(name)
                elif isinstance(val, ast.Subscript) \
                        and isinstance(val.value, ast.Name) \
                        and val.value.id in tainted:
                    tainted.add(name)       # a field view shares memory
                elif isinstance(val, ast.Call) \
                        and _tail(val) in _CLEANERS:
                    tainted.discard(name)
                else:
                    tainted.discard(name)
        for node in ast.walk(unit):
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
            elif isinstance(node, ast.AugAssign):
                target = node.target
            if isinstance(target, ast.Subscript):
                base = target.value
                if isinstance(base, ast.Name) and base.id in tainted:
                    findings.append(Finding(
                        rel, node.lineno, "E1213",
                        f"in-place write into {base.id!r}, a view of a "
                        "read-only store accessor — the array identity "
                        "is unchanged, so cached _Cell.shard device "
                        "placements keep serving the stale column and "
                        "copy-on-write forks see the mutation; write "
                        "through registry_writable()/set_* instead"))
                elif _accessor_call(base):
                    findings.append(Finding(
                        rel, node.lineno, "E1213",
                        "in-place write into a read-only store "
                        "accessor's return — write through "
                        "registry_writable()/set_* so the placement "
                        "retires with a fresh identity"))
            if isinstance(node, ast.Call) \
                    and _tail(node) in ("copyto", "put") \
                    and node.args \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in tainted:
                findings.append(Finding(
                    rel, node.lineno, "E1213",
                    f"np.{_tail(node)} into {node.args[0].id!r}, a "
                    "view of a read-only store accessor — in-place "
                    "scatter keeps the identity; cached placements "
                    "would not retire"))
            if isinstance(node, ast.Call) and _tail(node) == "at" \
                    and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Attribute) \
                    and node.func.value.attr == "add" \
                    and node.args \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in tainted:
                findings.append(Finding(
                    rel, node.lineno, "E1213",
                    f"np.add.at into {node.args[0].id!r}, a view of a "
                    "read-only store accessor — in-place scatter keeps "
                    "the identity; cached placements would not retire"))
    return findings


# ---------------------------------------------------------------------------
# 3. Happens-before write-ordering (E1221/E1222/E1223)
# ---------------------------------------------------------------------------

_WRITE_TAILS = {"atomic_write_bytes", "atomic_write_json",
                "atomic_replace_bytes"}
_JOURNAL_EVENT_KINDS = {"TICK", "BLOCK", "ATTESTATION", "SLASHING"}


def _arg_contains_tail(call, tail):
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for n in ast.walk(arg):
            if isinstance(n, ast.Call) and _tail(n) == tail:
                return True
    return False


def _persistence_events(fn_node):
    """Ordered (pos, kind, lineno) persistence effects of one function:
    blob/manifest writes, journal event appends, STEP commits, fsyncs
    and final-path renames."""
    events = []
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        tail = _tail(node)
        owner = _owner(node)
        pos = _pos(node)
        if tail in _WRITE_TAILS or tail == "_write_blob" \
                or (tail == "open" and len(node.args) >= 2
                    and isinstance(node.args[1], ast.Constant)
                    and isinstance(node.args[1].value, str)
                    and node.args[1].value.startswith(("w", "a", "x"))):
            if _arg_contains_tail(node, "manifest_path"):
                events.append((pos, "manifest", node.lineno))
            elif _arg_contains_tail(node, "blob_path") \
                    or tail == "_write_blob":
                events.append((pos, "blob", node.lineno))
        if tail == "frame" and node.args:
            kind = node.args[0]
            if isinstance(kind, ast.Name) and kind.id == "STEP" \
                    or isinstance(kind, ast.Attribute) \
                    and kind.attr == "STEP":
                # the marker WRITER (must fsync after the write)
                events.append((pos, "stepw", node.lineno))
            else:
                events.append((pos, "append", node.lineno))
        elif tail == "commit_step":
            # a caller delegating to the writer's discipline
            events.append((pos, "step", node.lineno))
        elif tail == "append" and node.args and (
                owner and "journal" in owner.lower()
                or isinstance(node.args[0], (ast.Name, ast.Attribute))
                and (getattr(node.args[0], "id", None)
                     in _JOURNAL_EVENT_KINDS
                     or getattr(node.args[0], "attr", None)
                     in _JOURNAL_EVENT_KINDS)):
            events.append((pos, "append", node.lineno))
        if tail in ("fsync", "fsync_dir"):
            events.append((pos, "fsync", node.lineno))
        if tail in ("replace", "rename") and owner == "os":
            events.append((pos, "rename", node.lineno))
    events.sort(key=lambda e: e[0])
    return events


def analyze_ordering(rel, tree, fsync_scope=False):
    """(findings, verdicts) for one recovery-surface module.
    ``fsync_scope``: apply the E1223 fsync-before-rename rule (the
    durable recovery surfaces only — bulk generator outputs are fenced
    by the INCOMPLETE-tag protocol instead)."""
    findings = []
    verdicts = []
    fns = [n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in fns:
        events = _persistence_events(fn)
        kinds = [k for _, k, _ in events]
        if "manifest" in kinds:
            manifest_pos = max(p for p, k, _ in events if k == "manifest")
            late = [(p, k, ln) for p, k, ln in events
                    if k == "blob" and p > manifest_pos]
            for _, _, lineno in late:
                findings.append(Finding(
                    rel, lineno, "E1221",
                    f"checkpoint blob written AFTER the manifest in "
                    f"{fn.name} — the manifest is the commit point and "
                    "must land last; a crash between them publishes a "
                    "generation whose recorded blob set is incomplete"))
            if "blob" in kinds and not late:
                n_blobs = kinds.count("blob")
                verdicts.append(
                    f"  [PROVEN] {rel}::{fn.name}: manifest-written-"
                    f"last ({n_blobs} blob write(s) precede the "
                    "manifest; no persistence effect follows)")
        markers = [p for p, k, _ in events if k in ("step", "stepw")]
        if markers and "append" in kinds:
            first_step = min(markers)
            bad = [(p, lineno) for p, k, lineno in events
                   if k == "append" and p > first_step]
            for _, lineno in bad:
                findings.append(Finding(
                    rel, lineno, "E1222",
                    f"journal event record appended AFTER the STEP "
                    f"commit marker in {fn.name} — the marker "
                    "certifies its preceding records; a record after "
                    "it belongs to the next step and would be "
                    "replayed out of order"))
            if not bad:
                verdicts.append(
                    f"  [PROVEN] {rel}::{fn.name}: journal records "
                    "precede their STEP commit marker")
        if "stepw" in kinds:
            step_pos = max(p for p, k, _ in events if k == "stepw")
            if not any(k == "fsync" and p > step_pos
                       for p, k, _ in events):
                findings.append(Finding(
                    rel, fn.lineno, "E1222",
                    f"{fn.name} writes a STEP commit marker with no "
                    "fsync after it — the durability boundary is the "
                    "fsynced marker; without it a crash can lose a "
                    "committed step"))
            else:
                verdicts.append(
                    f"  [PROVEN] {rel}::{fn.name}: STEP marker "
                    "fsynced (durability boundary holds)")
        if fsync_scope and "rename" in kinds:
            for p, k, lineno in events:
                if k != "rename":
                    continue
                if not any(kk == "fsync" and pp < p
                           for pp, kk, _ in events):
                    findings.append(Finding(
                        rel, lineno, "E1223",
                        f"os.replace/os.rename in {fn.name} with no "
                        "preceding fsync — the name can become durable "
                        "before the data, publishing a torn file after "
                        "a power cut; fsync the temp file first "
                        "(recovery/atomic.py discipline)"))
                else:
                    verdicts.append(
                        f"  [PROVEN] {rel}::{fn.name}: fsync-before-"
                        "rename holds")
    return findings, verdicts
