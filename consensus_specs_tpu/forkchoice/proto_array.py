"""Incremental proto-array LMD-GHOST fork choice.

The spec-shaped ``get_head`` (``forks/fork_choice.py``) recomputes
everything from scratch: every child at every tree level pays a
``get_weight`` that loops over *all active validators* and walks
``get_ancestor`` parent links per vote — O(blocks x validators x depth)
per head recompute.  Every production client solved this with
protolambda's proto-array design (the flat-array, delta-propagating
realization of the GHOST rule from Buterin et al., arXiv:2003.03052):
keep the block tree as a flat node array, keep per-node subtree weights,
and on each recompute apply only the *vote deltas* accrued since the
last one, then refresh best-child/best-descendant links in one backward
sweep — O(#changed votes + #nodes).

This module is that engine, in the columnar numpy style of the epoch
engine (``ops/epoch_kernels``):

* one node array: parent index, slot, epoch columns (block epoch,
  realized and unrealized justification epochs), exact python-int
  subtree weights, and per-sweep viability / best-child /
  best-descendant columns;
* one validator vote array: applied vote target (node index) and applied
  vote weight, int64 lanes;
* vote weights come columnar from the justified checkpoint state via
  ``state/arrays.py`` (the canonical copy-on-write struct-of-arrays
  store the epoch engine and hash forest share), so a
  justified-checkpoint change is ONE vectorized balance-delta pass, not
  a million python iterations;
* proposer boost is a virtual vote applied/removed through the same
  delta path; equivocations zero a validator's lane; finalization prunes
  the array to the finalized subtree.

Exactness contract: ``get_head`` / ``get_weight`` /
``get_filtered_block_tree`` return byte-identical results to the spec
loops (enforced by ``tests/phase0/fork_choice/``'s randomized
differential suite).  Anything the flat array cannot represent — a root
outside the pruned window, a weight column that could overflow an int64
lane — falls back to the spec loop for that call instead of answering
wrong.

Layering mirrors ``ops/epoch_kernels``:

  use_proto() / use_spec() / use_auto()   runtime switch; auto (the
      default) is ON unless ``CS_TPU_PROTO_ARRAY=0``
  install_forkchoice_accel(cls)           wraps a spec class's
      fork-choice surface with the dispatch plus the store-attached
      bookkeeping (incremental children index, memoized ancestor
      walks).  Applied by ``forks.install_engines`` to the
      hand-written ``ForkChoiceMixin`` at definition time and to each
      markdown-compiled class, so neither ladder's method bodies carry
      dispatch calls.
"""
import functools

import numpy as np

from consensus_specs_tpu import faults, supervisor
from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.obs.tracing import span
from consensus_specs_tpu.state import arrays as state_arrays
from consensus_specs_tpu.utils import env_flags
from consensus_specs_tpu.utils.ssz import hash_tree_root

_ZERO_ROOT = b"\x00" * 32
# python-int magnitude bound for the int64 weight lanes: a single
# effective balance above this (or a registry summing above it) falls
# back to the spec loop instead of risking a wrapped lane.
_WEIGHT_GUARD = 1 << 60

# ---------------------------------------------------------------------------
# Runtime switch (mirrors epoch_kernels' use_vectorized/use_loops/use_auto)
# ---------------------------------------------------------------------------

_mode = "auto"


def use_proto() -> None:
    """Force the proto-array engine on (guards can still fall back)."""
    global _mode
    _mode = "on"


def use_spec() -> None:
    """Force the spec-loop fork choice (the differential oracle)."""
    global _mode
    _mode = "off"


def use_auto() -> None:
    """Default policy: on unless ``CS_TPU_PROTO_ARRAY=0``."""
    global _mode
    _mode = "auto"


def enabled() -> bool:
    if _mode == "on":
        return True
    if _mode == "off":
        return False
    return env_flags.switch("CS_TPU_PROTO_ARRAY")


def backend_name() -> str:
    return "proto_array" if enabled() else "spec"


# engine-hit / spec-loop counters; the differential suite and the
# bench smoke assert on these so a silent fallback cannot turn the
# comparisons into loop-vs-loop tautologies.  Registered in the obs
# metrics registry with the read surface labeled by answer path
# (``forkchoice.head{path=engine|spec}`` ...), series pre-bound at
# module scope (speclint O5xx hot-path rule).
_C_HEAD_ENGINE = obs_registry.counter("forkchoice.head").labels(path="engine")
_C_HEAD_SPEC = obs_registry.counter("forkchoice.head").labels(path="spec")
_C_WEIGHT_ENGINE = obs_registry.counter(
    "forkchoice.weight").labels(path="engine")
_C_WEIGHT_SPEC = obs_registry.counter("forkchoice.weight").labels(path="spec")
_C_TREE_ENGINE = obs_registry.counter(
    "forkchoice.filtered_tree").labels(path="engine")
_C_TREE_SPEC = obs_registry.counter(
    "forkchoice.filtered_tree").labels(path="spec")
_C_REFRESHES = obs_registry.counter("forkchoice.refreshes").labels()
_C_VOTE_DELTAS = obs_registry.counter("forkchoice.vote_deltas").labels()
_C_BALANCE_PASSES = obs_registry.counter("forkchoice.balance_passes").labels()
_C_BOOST_DELTAS = obs_registry.counter("forkchoice.boost_deltas").labels()
_C_PRUNES = obs_registry.counter("forkchoice.prunes").labels()
_C_PRUNED_NODES = obs_registry.counter("forkchoice.pruned_nodes").labels()
# reason-labeled fallback accounting: ``guard`` for organic refusals (a
# guard tripped or the justified root left the array window),
# ``injected`` for harness-scheduled faults (consensus_specs_tpu/faults)
_C_FALLBACKS_ALL = obs_registry.counter("forkchoice.fallbacks")
_FALLBACKS = {
    "guard": _C_FALLBACKS_ALL.labels(reason="guard"),
    "injected": _C_FALLBACKS_ALL.labels(reason="injected"),
    "deadline": _C_FALLBACKS_ALL.labels(reason="deadline"),
}
_C_ANC_HIT = obs_registry.counter("cache.hit").labels(cache="fc_ancestors")
_C_ANC_MISS = obs_registry.counter("cache.miss").labels(cache="fc_ancestors")


def stats() -> dict:
    """Back-compat alias view of the ``forkchoice.*`` registry metrics
    (the differential suite and bench smoke assert on these keys)."""
    return {"proto_heads": _C_HEAD_ENGINE.n, "spec_heads": _C_HEAD_SPEC.n,
            "proto_weights": _C_WEIGHT_ENGINE.n,
            "spec_weights": _C_WEIGHT_SPEC.n,
            "proto_trees": _C_TREE_ENGINE.n, "spec_trees": _C_TREE_SPEC.n,
            "refreshes": _C_REFRESHES.n, "vote_deltas": _C_VOTE_DELTAS.n,
            "balance_passes": _C_BALANCE_PASSES.n,
            "boost_deltas": _C_BOOST_DELTAS.n, "prunes": _C_PRUNES.n,
            "pruned_nodes": _C_PRUNED_NODES.n,
            "fallbacks": _C_FALLBACKS_ALL.total()}


def reset_stats() -> None:
    obs_registry.reset("forkchoice.")


class _Fallback(Exception):
    """A guard refused the array path for this call; the caller runs the
    spec loop instead (engine state is left consistent for retries)."""


def _ckpt_key(checkpoint):
    return (int(checkpoint.epoch), bytes(checkpoint.root))


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class ProtoArrayEngine:
    """Flat-array fork choice for one ``Store``.

    Nodes are appended in ``on_block`` order, so a parent's index is
    always below its children's — the invariant both the delta
    up-propagation and the best-descendant sweep iterate on.
    """

    def __init__(self, spec, store):
        # -- node columns (index-aligned) -----------------------------------
        self._roots = []        # bytes32 per node
        self._index = {}        # root -> node index
        self._parent = []       # parent node index, -1 at the array base
        self._slot = []         # int block slot
        self._weight = []       # EXACT python-int subtree weight (incl. boost)
        cap = 64
        self._block_e = np.zeros(cap, dtype=np.int64)    # block epoch
        self._state_e = np.zeros(cap, dtype=np.int64)    # realized just. epoch
        self._unreal_e = np.zeros(cap, dtype=np.int64)   # unrealized just. epoch
        self._n = 0
        # last-sweep outputs, kept for introspection/tests
        self.best_child = np.zeros(0, dtype=np.int64)
        self.best_descendant = np.zeros(0, dtype=np.int64)
        self.viable = np.zeros(0, dtype=bool)
        # -- validator vote lanes -------------------------------------------
        vcap = 1024
        self._vote_node = np.full(vcap, -1, dtype=np.int64)
        self._vote_weight = np.zeros(vcap, dtype=np.int64)
        self._equiv = np.zeros(vcap, dtype=bool)
        self._nv = vcap
        self._equiv_seen = set()
        self._dirty = set()     # validator indices with a possibly-new vote
        # -- refresh bookkeeping --------------------------------------------
        self._bal_key = None    # justified-checkpoint key of _bal_eff
        self._bal_eff = None    # int64 per-validator weight column
        self._boost = None      # applied (node, amount) proposer boost
        self._fin_seen = None   # finalized-checkpoint key already pruned for
        self._anc_cache = None  # (fin_epoch, fin_root, n) -> per-node ancestor
        self._delta = None      # pending per-node weight deltas (int64)
        self._broken = False    # structural desync: disabled permanently
        self._seen_blocks = 0   # unique roots ever appended (incl. pruned)
        for root in store.blocks:
            self._append_node(spec, store, bytes(root))

    # -- growth helpers -----------------------------------------------------

    def _grow_nodes(self, need: int) -> None:
        cap = self._block_e.size
        if need <= cap:
            return
        while cap < need:
            cap *= 2
        for name in ("_block_e", "_state_e", "_unreal_e"):
            old = getattr(self, name)
            arr = np.zeros(cap, dtype=np.int64)
            arr[:self._n] = old[:self._n]
            setattr(self, name, arr)

    def _grow_validators(self, need: int) -> None:
        if need <= self._nv:
            return
        cap = self._nv
        while cap < need:
            cap *= 2
        for name, fill in (("_vote_node", -1), ("_vote_weight", 0),
                           ("_equiv", False)):
            old = getattr(self, name)
            arr = np.full(cap, fill, dtype=old.dtype)
            arr[:self._nv] = old
            setattr(self, name, arr)
        if self._bal_eff is not None:
            bal = np.zeros(cap, dtype=np.int64)
            bal[:self._bal_eff.size] = self._bal_eff
            self._bal_eff = bal
        self._nv = cap

    # -- event hooks (called by the install wrappers) -----------------------

    def _append_node(self, spec, store, root: bytes) -> None:
        if self._broken or root in self._index:
            return
        block = store.blocks[root]
        parent = self._index.get(bytes(block.parent_root), -1)
        if parent < 0 and self._n > 0:
            # a non-base block whose parent the array has never seen:
            # structural desync (should be impossible via on_block)
            self._broken = True
            return
        idx = self._n
        self._grow_nodes(idx + 1)
        self._roots.append(root)
        self._index[root] = idx
        self._parent.append(parent)
        self._slot.append(int(block.slot))
        self._weight.append(0)
        self._block_e[idx] = int(spec.compute_epoch_at_slot(block.slot))
        state = store.block_states.get(root)
        self._state_e[idx] = (
            0 if state is None
            else int(state.current_justified_checkpoint.epoch))
        unreal = store.unrealized_justifications.get(root)
        self._unreal_e[idx] = 0 if unreal is None else int(unreal.epoch)
        self._n = idx + 1
        self._seen_blocks += 1
        self._anc_cache = None

    def note_block(self, spec, store, root: bytes) -> None:
        self._append_node(spec, store, bytes(root))

    def note_votes(self, indices) -> None:
        self._dirty.update(int(i) for i in indices)

    def note_equivocations(self, store) -> None:
        for i in store.equivocating_indices:
            ii = int(i)
            if ii in self._equiv_seen:
                continue
            self._equiv_seen.add(ii)
            self._grow_validators(ii + 1)
            self._equiv[ii] = True
            if self._bal_eff is not None and ii < self._bal_eff.size:
                self._bal_eff[ii] = 0
            self._dirty.add(ii)

    # -- refresh: prune + deltas + propagation ------------------------------

    def _get_delta(self) -> np.ndarray:
        if self._delta is None or self._delta.size < self._n:
            delta = np.zeros(self._n, dtype=np.int64)
            if self._delta is not None:
                delta[:self._delta.size] = self._delta
            self._delta = delta
        return self._delta

    def _prune(self, store) -> None:
        """Drop everything outside the finalized subtree and reindex."""
        froot = bytes(store.finalized_checkpoint.root)
        fidx = self._index.get(froot)
        if fidx is None:
            self._broken = True
            return
        if fidx == 0:
            return
        n = self._n
        keep = [False] * n
        keep[fidx] = True
        for i in range(fidx + 1, n):
            p = self._parent[i]
            keep[i] = p >= 0 and keep[p]
        remap = np.full(n, -1, dtype=np.int64)
        new_roots, new_parent, new_slot, new_weight = [], [], [], []
        for i in range(n):
            if not keep[i]:
                continue
            remap[i] = len(new_roots)
            new_roots.append(self._roots[i])
            p = self._parent[i]
            new_parent.append(int(remap[p]) if i != fidx else -1)
            new_slot.append(self._slot[i])
            new_weight.append(self._weight[i])
        kept = np.nonzero(remap >= 0)[0]
        m = kept.size
        for name in ("_block_e", "_state_e", "_unreal_e"):
            arr = getattr(self, name)
            compact = np.zeros(max(arr.size, 64), dtype=np.int64)
            compact[:m] = arr[kept]
            setattr(self, name, compact)
        self._roots = new_roots
        self._parent = new_parent
        self._slot = new_slot
        self._weight = new_weight
        self._index = {r: i for i, r in enumerate(new_roots)}
        self._n = m
        # votes (and the boost) targeting pruned nodes contributed weight
        # only to pruned nodes, so they are dropped with no delta
        mask = self._vote_node >= 0
        self._vote_node[mask] = remap[self._vote_node[mask]]
        dropped = mask & (self._vote_node < 0)
        self._vote_weight[dropped] = 0
        if self._boost is not None:
            node, amount = self._boost
            node = int(remap[node])
            self._boost = (node, amount) if node >= 0 else None
        if self._delta is not None:
            padded = np.zeros(n, dtype=np.int64)
            k = min(self._delta.size, n)
            padded[:k] = self._delta[:k]
            self._delta = padded[kept]
        self._anc_cache = None
        _C_PRUNES.add()
        _C_PRUNED_NODES.add(n - m)

    def _balance_column(self, spec, state) -> np.ndarray:
        """Per-validator vote weight from the justified state: effective
        balance where active and not slashed, else 0 — exactly the set
        the spec's ``get_weight`` loop iterates.  Columns come from the
        justified state's attached ``StateArrays`` store, shared with
        the epoch engine and the hash forest; checkpoint states derived
        by state copies inherit their parent's columns copy-on-write,
        so a justified-checkpoint change typically re-walks nothing."""
        cols = state_arrays.registry_of(state)
        epoch = int(spec.get_current_epoch(state))
        eff = cols["eff"]
        if eff.size and int(eff.max()) > _WEIGHT_GUARD:
            raise _Fallback()
        active = (cols["act"] <= np.uint64(epoch)) \
            & (np.uint64(epoch) < cols["ext"])
        bal = np.where(active & ~cols["sl"], eff, 0).astype(np.int64)
        if float(bal.sum(dtype=np.float64)) > float(_WEIGHT_GUARD):
            raise _Fallback()
        return bal

    def _refresh(self, spec, store) -> None:
        """Bring node weights up to date with the store: one columnar
        balance-delta pass (justified checkpoint changed), one loop over
        the changed votes, one boost adjustment, one backward
        up-propagation."""
        _C_REFRESHES.add()
        # a consumer that inserted into store.blocks directly (bypassing
        # the wrapped on_block) would leave the array blind to those
        # blocks; spec stores never delete, so unique-roots-ever-seen
        # must equal the dict size — anything else answers via the spec
        # loop instead of from a stale tree
        if len(store.blocks) != self._seen_blocks:
            raise _Fallback()
        fk = _ckpt_key(store.finalized_checkpoint)
        if fk != self._fin_seen:
            self._prune(store)
            if self._broken:
                raise _Fallback()
            self._fin_seen = fk

        # the spec's get_weight opens with this lookup too, but its
        # get_head can still succeed without it (a filtered tree with no
        # children never weighs anything) — so a missing justified
        # checkpoint state falls back to the spec loop instead of
        # raising where the spec would not
        jk = _ckpt_key(store.justified_checkpoint)
        try:
            justified_state = store.checkpoint_states[jk]
        except KeyError:
            raise _Fallback()
        if jk != self._bal_key:
            bal = self._balance_column(spec, justified_state)
            self._grow_validators(bal.size)
            bal_eff = np.zeros(self._nv, dtype=np.int64)
            bal_eff[:bal.size] = bal
            bal_eff[self._equiv] = 0
            mask = self._vote_node >= 0
            changed = mask & (self._vote_weight != bal_eff)
            idx = np.nonzero(changed)[0]
            if idx.size:
                delta = self._get_delta()
                np.add.at(delta, self._vote_node[idx],
                          bal_eff[idx] - self._vote_weight[idx])
                self._vote_weight[idx] = bal_eff[idx]
            self._bal_eff = bal_eff
            self._bal_key = jk
            _C_BALANCE_PASSES.add()

        if self._dirty:
            bal_eff = self._bal_eff
            index = self._index
            for i in self._dirty:
                if i >= self._nv:
                    self._grow_validators(i + 1)
                    bal_eff = self._bal_eff
                msg = store.latest_messages.get(i)
                node = -1 if msg is None else index.get(bytes(msg.root), -1)
                new_w = int(bal_eff[i]) if node >= 0 else 0
                old_n = int(self._vote_node[i])
                old_w = int(self._vote_weight[i])
                if node == old_n and new_w == old_w:
                    continue
                delta = self._get_delta()
                if old_n >= 0:
                    delta[old_n] -= old_w
                if node >= 0:
                    delta[node] += new_w
                self._vote_node[i] = node
                self._vote_weight[i] = new_w
                _C_VOTE_DELTAS.add()
            self._dirty.clear()

        # proposer boost: a virtual vote worth get_proposer_score,
        # applied/removed through the same delta path
        broot = bytes(store.proposer_boost_root)
        if broot == _ZERO_ROOT:
            desired = None
        else:
            node = self._index.get(broot)
            if node is None:
                raise _Fallback()
            desired = (node, int(spec.get_proposer_score(store)))
        if desired != self._boost:
            delta = self._get_delta()
            if self._boost is not None:
                delta[self._boost[0]] -= self._boost[1]
            if desired is not None:
                delta[desired[0]] += desired[1]
            self._boost = desired
            _C_BOOST_DELTAS.add()

        if self._delta is not None:
            # through _get_delta(): a held-over delta array (a prior
            # refresh fell back after queuing deltas, then nodes were
            # appended) may be shorter than _n
            dl = self._get_delta()[:self._n].tolist()
            weight = self._weight
            parent = self._parent
            for i in range(self._n - 1, -1, -1):
                d = dl[i]
                if d:
                    weight[i] += d
                    p = parent[i]
                    if p >= 0:
                        dl[p] += d
            self._delta = None

    # -- viability + sweep --------------------------------------------------

    def _finalized_ancestors(self, spec, store) -> list:
        """Per-node index of ``get_checkpoint_block(store, node,
        finalized_epoch)`` within the array, via one forward pass
        (parents precede children)."""
        fe = int(store.finalized_checkpoint.epoch)
        froot = bytes(store.finalized_checkpoint.root)
        key = (fe, froot, self._n)
        if self._anc_cache is not None and self._anc_cache[0] == key:
            return self._anc_cache[1]
        start = int(spec.compute_start_slot_at_epoch(fe))
        anc = [0] * self._n
        slot = self._slot
        parent = self._parent
        for i in range(self._n):
            p = parent[i]
            anc[i] = i if (slot[i] <= start or p < 0) else anc[p]
        self._anc_cache = (key, anc)
        return anc

    def _leaf_viable(self, spec, store) -> np.ndarray:
        """Vectorized ``_leaf_viable`` over every node: the voting-source
        pull-up, the justification-epoch window, and the finalized-
        checkpoint ancestry check."""
        n = self._n
        cur_e = int(spec.get_current_store_epoch(store))
        genesis = int(spec.GENESIS_EPOCH)
        je = int(store.justified_checkpoint.epoch)
        fe = int(store.finalized_checkpoint.epoch)
        be = self._block_e[:n]
        vs = np.where(be < cur_e, self._unreal_e[:n], self._state_e[:n])
        correct_justified = (vs == je) | (vs + 2 >= cur_e) if je != genesis \
            else np.ones(n, dtype=bool)
        if fe == genesis:
            correct_finalized = np.ones(n, dtype=bool)
        else:
            froot = bytes(store.finalized_checkpoint.root)
            anc = self._finalized_ancestors(spec, store)
            roots = self._roots
            correct_finalized = np.fromiter(
                (roots[anc[i]] == froot for i in range(n)),
                dtype=bool, count=n)
        return correct_justified & correct_finalized

    def _sweep(self, spec, store):
        """One backward pass: leaf-viability aggregation (a subtree is
        kept iff some leaf in it is viable — the spec's
        ``filter_block_tree``) plus best-child / best-descendant links
        with the spec's ``(weight, root)`` tie-break."""
        n = self._n
        lv = self._leaf_viable(spec, store).tolist()
        viable = [False] * n
        child_or = [False] * n
        has_child = [False] * n
        best_child = [-1] * n
        best_key = [None] * n
        weight = self._weight
        roots = self._roots
        parent = self._parent
        for i in range(n - 1, -1, -1):
            v = child_or[i] if has_child[i] else lv[i]
            viable[i] = v
            p = parent[i]
            if p >= 0:
                has_child[p] = True
                if v:
                    child_or[p] = True
                    k = (weight[i], roots[i])
                    if best_key[p] is None or k > best_key[p]:
                        best_key[p] = k
                        best_child[p] = i
        best_desc = list(range(n))
        # children first (higher indices), so a parent's link is chased
        # through an already-resolved child
        for i in range(n - 1, -1, -1):
            if best_child[i] >= 0:
                best_desc[i] = best_desc[best_child[i]]
        self.best_child = np.array(best_child, dtype=np.int64)
        self.best_descendant = np.array(best_desc, dtype=np.int64)
        self.viable = np.array(viable, dtype=bool)
        return viable, best_child, best_desc

    # -- spec-surface answers ----------------------------------------------

    def head(self, spec, store):
        """Root of the canonical head, or None to fall back."""
        if self._broken or not supervisor.admit("forkchoice.head"):
            return None
        try:
            faults.check("forkchoice.head")
            with supervisor.deadline_scope("forkchoice.head"):
                self._refresh(spec, store)
                # boundary: a pathologically slow refresh (vote deltas,
                # prune) converts into a counted fallback before the
                # sweep runs
                supervisor.deadline_check()
        except (_Fallback, faults.InjectedFault,
                supervisor.DeadlineExceeded) as exc:
            faults.count_fallback(_FALLBACKS, exc, site="forkchoice.head")
            return None
        j = self._index.get(bytes(store.justified_checkpoint.root))
        if j is None:
            faults.count_fallback(_FALLBACKS, site="forkchoice.head")
            return None
        _, _, best_desc = self._sweep(spec, store)
        head = self._roots[best_desc[j]]
        if faults.corrupt_armed("forkchoice.head"):
            # silent-corruption injection (sentinel-audit test vector):
            # a byte-flipped root — deterministically wrong
            head = bytes(head[:31]) + bytes([head[31] ^ 1])
        supervisor.note_success("forkchoice.head")
        return head

    def weight(self, spec, store, root: bytes):
        """Subtree weight of ``root`` (boost included), or None."""
        if self._broken or not supervisor.admit("forkchoice.weight"):
            return None
        try:
            faults.check("forkchoice.weight")
            with supervisor.deadline_scope("forkchoice.weight"):
                self._refresh(spec, store)
                supervisor.deadline_check()
        except (_Fallback, faults.InjectedFault,
                supervisor.DeadlineExceeded) as exc:
            faults.count_fallback(_FALLBACKS, exc, site="forkchoice.weight")
            return None
        # look up only after _refresh: a prune inside it compacts the
        # arrays and remaps every index
        idx = self._index.get(bytes(root))
        if idx is None:
            # breaker-neutral on purpose, unlike head/filtered_tree's
            # justified-root miss: an unknown/pruned QUERY root says
            # nothing about engine health, and counting it as a failure
            # would let repeated unknown-root queries demote (or, in
            # half-open, re-open) a healthy engine
            return None
        supervisor.note_success("forkchoice.weight")
        return self._weight[idx]

    def filtered_block_tree(self, spec, store):
        """The spec's ``get_filtered_block_tree`` dict, or None."""
        if self._broken or not supervisor.admit("forkchoice.filtered_tree"):
            return None
        try:
            faults.check("forkchoice.filtered_tree")
            with supervisor.deadline_scope("forkchoice.filtered_tree"):
                self._refresh(spec, store)
                supervisor.deadline_check()
        except (_Fallback, faults.InjectedFault,
                supervisor.DeadlineExceeded) as exc:
            faults.count_fallback(_FALLBACKS, exc,
                                  site="forkchoice.filtered_tree")
            return None
        j = self._index.get(bytes(store.justified_checkpoint.root))
        if j is None:
            faults.count_fallback(_FALLBACKS, site="forkchoice.filtered_tree")
            return None
        viable, _, _ = self._sweep(spec, store)
        n = self._n
        parent = self._parent
        roots = self._roots
        in_tree = [False] * n
        in_tree[j] = True
        out = {}
        for i in range(j, n):
            if i != j:
                p = parent[i]
                in_tree[i] = p >= 0 and in_tree[p]
            if in_tree[i] and viable[i]:
                out[roots[i]] = store.blocks[roots[i]]
        supervisor.note_success("forkchoice.filtered_tree")
        return out


# ---------------------------------------------------------------------------
# Installation: wrap a spec class's fork-choice surface
# ---------------------------------------------------------------------------

def _engine(store):
    """The store's engine, for READ dispatch: honors the runtime switch
    and the supervisor's audit-probe flag (a sentinel audit's spec-loop
    replay must not recurse into the engine under audit)."""
    if not enabled() or supervisor.probing():
        return None
    eng = getattr(store, "_fc_proto", None)
    if eng is not None and eng._broken:
        return None
    return eng


def attach_store_accel(spec, store) -> None:
    """Attach the engine + store bookkeeping to a store NOT built
    through the wrapped ``get_forkchoice_store`` — a checkpoint restore
    (``recovery/checkpoint.py``).  The children index rebuilds from the
    blocks map (whose insertion order IS the original ``on_block``
    order, so the engine's parent-before-child node invariant holds),
    and the engine is seeded with every existing vote and equivocation
    so the first head read after a restore answers identically to the
    store that was checkpointed."""
    children = {}
    for root, block in store.blocks.items():
        children.setdefault(bytes(block.parent_root), []) \
            .append(bytes(root))
    store._fc_children = children
    store._fc_children_n = len(store.blocks)
    store._fc_ancestors = {}
    if enabled():
        eng = ProtoArrayEngine(spec, store)
        eng.note_votes(list(store.latest_messages.keys()))
        eng.note_equivocations(store)
        store._fc_proto = eng


def install_forkchoice_accel(cls) -> None:
    """Wrap ``cls``'s own fork-choice methods with the proto-array
    dispatch and the store-attached bookkeeping (incremental
    parent->children index, memoized ``get_ancestor``).  Applied by
    ``forks.install_engines`` on both ladders: the hand-written
    ``ForkChoiceMixin`` (at definition time) and each markdown-compiled
    class, whose method bodies are emitted verbatim from the spec text.
    Only methods defined on ``cls`` itself are wrapped (inherited ones
    are already wrapped on the base class); wrapping is idempotent.

    Write-side hooks (``on_block`` / ``update_latest_messages`` /
    ``on_attester_slashing``) feed the engine whenever it is attached,
    regardless of the runtime switch, so flipping ``use_spec()`` /
    ``use_proto()`` mid-stream (the differential suite does) never
    desyncs it.  Read-side dispatch (``get_head`` / ``get_weight`` /
    ``get_filtered_block_tree``) honors the switch.  The bookkeeping
    caches (children index, ancestor memo) are behavior-preserving and
    stay on in both modes; ``CS_TPU_PROTO_ARRAY=0`` at store-creation
    time skips attaching the engine entirely."""

    def wrap(name, make):
        fn = cls.__dict__.get(name)
        if fn is None or getattr(fn, "_fc_accel_wrapper", False):
            return
        wrapper = functools.wraps(fn)(make(fn))
        wrapper._fc_accel_wrapper = True
        setattr(cls, name, wrapper)

    def make_get_forkchoice_store(orig):
        def get_forkchoice_store(self, anchor_state, anchor_block):
            store = orig(self, anchor_state, anchor_block)
            children = {}
            for root, block in store.blocks.items():
                children.setdefault(bytes(block.parent_root), []) \
                    .append(bytes(root))
            store._fc_children = children
            store._fc_children_n = len(store.blocks)
            store._fc_ancestors = {}
            if enabled():
                store._fc_proto = ProtoArrayEngine(self, store)
            return store
        return get_forkchoice_store

    def make_on_block(orig):
        def on_block(self, store, signed_block):
            orig(self, store, signed_block)
            # only reached when every on_block assertion passed
            block = signed_block.message
            root = bytes(hash_tree_root(block))
            children = getattr(store, "_fc_children", None)
            if children is not None:
                siblings = children.setdefault(bytes(block.parent_root), [])
                if root not in siblings:
                    siblings.append(root)
                store._fc_children_n = len(store.blocks)
            eng = getattr(store, "_fc_proto", None)
            if eng is not None:
                eng.note_block(self, store, root)
        return on_block

    def make_update_latest_messages(orig):
        def update_latest_messages(self, store, attesting_indices,
                                   attestation):
            orig(self, store, attesting_indices, attestation)
            eng = getattr(store, "_fc_proto", None)
            if eng is not None:
                eng.note_votes(attesting_indices)
        return update_latest_messages

    def make_on_attester_slashing(orig):
        def on_attester_slashing(self, store, attester_slashing):
            orig(self, store, attester_slashing)
            eng = getattr(store, "_fc_proto", None)
            if eng is not None:
                eng.note_equivocations(store)
        return on_attester_slashing

    def make_get_ancestor(orig):
        def get_ancestor(self, store, root, slot):
            cache = getattr(store, "_fc_ancestors", None)
            if cache is None:
                return orig(self, store, root, slot)
            # ancestry never changes, but the memo would otherwise grow
            # with blocks x distinct-slots-queried forever; clearing at
            # each finalization advance bounds it to one finality window
            # (it rebuilds lazily, O(1) amortized per walk)
            fin_epoch = int(store.finalized_checkpoint.epoch)
            if getattr(store, "_fc_ancestors_fin", None) != fin_epoch:
                cache.clear()
                store._fc_ancestors_fin = fin_epoch
            root = bytes(root)
            slot_i = int(slot)
            hit = cache.get((root, slot_i))
            if hit is not None:
                _C_ANC_HIT.add()
                return self.Root(hit)
            _C_ANC_MISS.add()
            # the spec's iterative walk, memoizing every visited link so
            # repeated per-vote walks are O(1) amortized
            path = []
            r = root
            block = store.blocks[r]
            while block.slot > slot_i:
                path.append(r)
                r = bytes(block.parent_root)
                hit = cache.get((r, slot_i))
                if hit is not None:
                    r = hit
                    break
                block = store.blocks[r]
            for p in path:
                cache[(p, slot_i)] = r
            return self.Root(r)
        return get_ancestor

    def make_children_index(orig):
        def _children_index(self, store):
            children = getattr(store, "_fc_children", None)
            # freshness guard: a consumer inserting into store.blocks
            # directly (bypassing the wrapped on_block) must get the
            # spec's from-scratch rebuild, never a stale index
            if children is not None \
                    and getattr(store, "_fc_children_n", -1) \
                    == len(store.blocks):
                return children
            return orig(self, store)
        return _children_index

    def make_get_head(orig):
        def get_head(self, store):
            with span("forkchoice.get_head"):
                eng = _engine(store)
                if eng is not None:
                    head = eng.head(self, store)
                    if head is not None:
                        if supervisor.audit_due("forkchoice.head"):
                            # sentinel audit: the spec loop's answer is
                            # authoritative; a divergent engine head is
                            # quarantined, never served
                            with supervisor.probe():
                                spec_head = orig(self, store)
                            supervisor.audit_result(
                                "forkchoice.head",
                                bytes(spec_head) == bytes(head),
                                "engine head diverged from the spec loop")
                            _C_HEAD_SPEC.add()
                            return spec_head
                        _C_HEAD_ENGINE.add()
                        return self.Root(head)
                _C_HEAD_SPEC.add()
                return orig(self, store)
        return get_head

    def make_get_weight(orig):
        def get_weight(self, store, root):
            eng = _engine(store)
            if eng is not None:
                w = eng.weight(self, store, root)
                if w is not None:
                    if supervisor.audit_due("forkchoice.weight"):
                        with supervisor.probe():
                            spec_w = orig(self, store, root)
                        supervisor.audit_result(
                            "forkchoice.weight", int(spec_w) == int(w),
                            "engine subtree weight diverged from the "
                            "spec loop")
                        _C_WEIGHT_SPEC.add()
                        return spec_w
                    _C_WEIGHT_ENGINE.add()
                    return self.Gwei(w)
            _C_WEIGHT_SPEC.add()
            return orig(self, store, root)
        return get_weight

    def make_get_filtered_block_tree(orig):
        def get_filtered_block_tree(self, store):
            eng = _engine(store)
            if eng is not None:
                tree = eng.filtered_block_tree(self, store)
                if tree is not None:
                    if supervisor.audit_due("forkchoice.filtered_tree"):
                        with supervisor.probe():
                            spec_tree = orig(self, store)
                        supervisor.audit_result(
                            "forkchoice.filtered_tree",
                            {bytes(k) for k in tree}
                            == {bytes(k) for k in spec_tree},
                            "engine filtered block tree diverged from "
                            "the spec loop")
                        _C_TREE_SPEC.add()
                        return spec_tree
                    _C_TREE_ENGINE.add()
                    return tree
            _C_TREE_SPEC.add()
            return orig(self, store)
        return get_filtered_block_tree

    wrap("get_forkchoice_store", make_get_forkchoice_store)
    wrap("on_block", make_on_block)
    wrap("update_latest_messages", make_update_latest_messages)
    wrap("on_attester_slashing", make_on_attester_slashing)
    wrap("get_ancestor", make_get_ancestor)
    wrap("_children_index", make_children_index)
    wrap("get_head", make_get_head)
    wrap("get_weight", make_get_weight)
    wrap("get_filtered_block_tree", make_get_filtered_block_tree)
