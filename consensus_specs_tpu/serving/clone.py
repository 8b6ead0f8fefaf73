"""Chunk-level state clones: whole-state snapshots at column-fork cost.

``Container.copy`` (utils/ssz/types.py) is structurally O(n) Python —
every element of every sequence gets a ``.copy()`` call and an owner
re-bind, so snapshotting a 1M-validator state costs millions of Python
method calls even though element copies of immutable leaves are no-ops
and the chunk trees copy as C-level bytearray memcpys.  The serving
pipeline snapshots a state per accepted block (and fork choice copies
those snapshots per child), so that loop is exactly the cost that caps
concurrent fork-choice heads.

:func:`clone_state` replaces the per-element walk with three per-field
policies:

* **fast** — sequences of immutable elements (``BasicValue`` ints,
  ``ByteVector``/``ByteList`` bytes).  Their base ``copy()`` is already
  ``[x.copy() for x in items]`` where every ``x.copy()`` returns ``x``;
  we produce the same result with one C-level ``list(items)`` plus the
  tree memcpy (``_copy_tree_into``) — byte-identical, none of the
  per-element interpreter work.
* **lazy** — large composite-element sequences (validators,
  historical summaries, ...).  The clone is an instance of a cached
  per-concrete-class subclass whose ``_items`` / ``_tree`` slots are
  shadowed by properties: element copies and the tree memcpy happen on
  first touch, against a strong reference to the frozen source.  A
  snapshot that is never mutated or re-merkleized (the common fate of
  ``store.block_states`` entries) never pays for either.  Elements
  whose fields are all immutable leaves (``Validator``) are copied with
  one C-level ``__dict__`` copy each, so a first read of a 2^20-entry
  registry costs one pass of object allocations, not a ``copy()`` call
  per field of every validator.
* **eager** — everything else (nested containers, bitfields, small
  sequences): the ordinary ``copy()``.

Laziness is only sound if the source cannot change under the clone, so
the lazy path carries a **frozen-source contract**: the source's
mutation generation (``_gen``) is recorded at clone time and re-checked
on every deferred touch; a mismatch raises ``RuntimeError`` instead of
silently materializing from a drifted source.  Computing a root on the
source does NOT trip the guard (root computation flushes chunk dirt
without bumping ``_gen``); any pending dirt is flushed into the
source's tree at clone time so a later lazy tree memcpy starts clean.
Post-state snapshots in the pipeline are frozen by construction —
fork choice only ever ``copy()``s them — which is why the contract
holds there.  Counters: ``serving.clones``, per-mode
``serving.clone_fields``, and ``serving.materializations`` (how much
of the deferred work was ever actually paid).
"""

import gc
import weakref

from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.utils import env_flags
from consensus_specs_tpu.utils.ssz.types import (
    BasicValue,
    ByteListBase,
    ByteVectorBase,
    Container,
    _SequenceBase,
    _set_owner,
)

_C_CLONES = obs_registry.counter("serving.clones").labels()
_C_FIELD_FAST = obs_registry.counter("serving.clone_fields").labels(mode="fast")
_C_FIELD_LAZY = obs_registry.counter("serving.clone_fields").labels(mode="lazy")
_C_FIELD_EAGER = obs_registry.counter("serving.clone_fields").labels(mode="eager")
_C_MAT_ITEMS = obs_registry.counter("serving.materializations").labels(stage="items")
_C_MAT_TREE = obs_registry.counter("serving.materializations").labels(stage="tree")

# Element types whose ``copy()`` returns ``self`` and which never hold
# an owner backref — the precondition for sharing them across clones.
_IMMUTABLE_ELEMS = (BasicValue, ByteVectorBase, ByteListBase)

# Composite sequences shorter than this are cheaper to copy eagerly
# than to wrap (the lazy wrapper costs a class lookup + dict setup).
_DEFAULT_LAZY_MIN = 64

# Sentinel for "tree not copied from the source yet" — distinct from
# None, which is a legal tree value ("rebuild from leaves on demand").
_TREE_UNSET = object()

_lazy_cache = {}            # concrete sequence class -> lazy subclass
_fast_cache = {}            # concrete sequence class -> fast subclass
_leaf_cache = {}            # element class -> all fields immutable leaves


def _lazy_min() -> int:
    raw = env_flags.knob("CS_TPU_SERVING_LAZY_MIN")
    try:
        return int(raw)
    except (TypeError, ValueError):
        return _DEFAULT_LAZY_MIN


def _flush_source_dirt(src) -> None:
    """Flush pending chunk dirt into the source's tree so deferred tree
    memcpys (and the shared items list) start from a clean layer.  Root
    maintenance does not bump ``_gen``, so this never trips the
    frozen-source guard."""
    if getattr(src, "_tree", None) is not None and getattr(src, "_dirty", None):
        src._tree_root()


def _leaf_container(et) -> bool:
    """``et`` is a container whose every field is an immutable leaf, so
    its ``copy()`` only re-binds the same field values."""
    leaf = _leaf_cache.get(et)
    if leaf is None:
        leaf = _leaf_cache[et] = issubclass(et, Container) and all(
            issubclass(t, _IMMUTABLE_ELEMS) for t in et._fields.values())
    return leaf


def _copy_elems(items, owner) -> list:
    """``[x.copy() for x in items]``, each owned by ``owner`` at its index.
    A leaf container's copy is its ``__dict__`` copied at C level: the
    same field values and memoized root ``copy()`` gives, ~7x cheaper."""
    if not _leaf_container(type(owner).elem_type):
        out = [x.copy() for x in items]
        for i, x in enumerate(out):
            _set_owner(x, owner, i)
        return out
    ref = weakref.ref(owner)
    new = object.__new__
    out = []
    # the pass makes no reference cycles: spare it the collector's
    # rescans of a heap that grows by a registry (half its cost)
    collecting = gc.isenabled()
    gc.disable()
    try:
        for i, x in enumerate(items):
            y = new(x.__class__)
            d = y.__dict__
            d.update(x.__dict__)
            d["_owner"] = (ref, i)
            out.append(y)
    finally:
        if collecting:
            gc.enable()
    return out


def _lazy_class(cls):
    lz = _lazy_cache.get(cls)
    if lz is not None:
        return lz

    def _check_src(self):
        d = self.__dict__
        src = d["_lz_src"]
        if src is None or getattr(src, "_gen", 0) != d["_lz_gen"]:
            raise RuntimeError(
                f"serving.clone: source {cls.__name__} mutated after a "
                "chunk-level clone; clone sources must stay frozen")
        return src

    def _maybe_release(self):
        # Once both halves are materialized the source is never touched
        # again — drop the strong ref so snapshots don't pin lineages.
        d = self.__dict__
        if d["_lz_items"] is not None and d["_lz_tree"] is not _TREE_UNSET:
            d["_lz_src"] = None

    def _materialize(self):
        items = _copy_elems(_check_src(self)._items, self)
        self.__dict__["_lz_items"] = items
        _C_MAT_ITEMS.add()
        _maybe_release(self)
        return items

    def _get_items(self):
        items = self.__dict__["_lz_items"]
        return items if items is not None else _materialize(self)

    def _set_items(self, value):
        self.__dict__["_lz_items"] = value
        _maybe_release(self)

    def _get_tree(self):
        d = self.__dict__
        t = d["_lz_tree"]
        if t is _TREE_UNSET:
            src = _check_src(self)
            st = getattr(src, "_tree", None)
            t = st.copy() if st is not None else None
            d["_lz_tree"] = t
            _C_MAT_TREE.add()
            _maybe_release(self)
        return t

    def _set_tree(self, value):
        self.__dict__["_lz_tree"] = value
        _maybe_release(self)

    def _len(self):
        items = self.__dict__["_lz_items"]
        if items is not None:
            return len(items)
        return len(_check_src(self)._items)

    def _copy(self):
        d = self.__dict__
        if d["_lz_items"] is None:
            # Still virtual: another lazy clone off the same frozen
            # source — clone chains stay O(1) until someone writes.
            _C_FIELD_LAZY.add()
            return _lazy_sequence_clone(_check_src(self))
        # Materialized: behave exactly like the base-class copy, and
        # produce a PLAIN instance so laziness doesn't nest.
        new = object.__new__(cls)
        object.__setattr__(new, "_items", _copy_elems(d["_lz_items"], new))
        _SequenceBase._copy_tree_into(self, new)
        return new

    lz = type(
        "_LazyClone_" + cls.__name__, (cls,),
        {
            "_serving_lazy": True,
            "_items": property(_get_items, _set_items),
            "_tree": property(_get_tree, _set_tree),
            "__len__": _len,
            "copy": _copy,
        },
    )
    _lazy_cache[cls] = lz
    return lz


def _lazy_sequence_clone(src):
    _flush_source_dirt(src)
    new = object.__new__(_lazy_class(type(src)))
    d = new.__dict__
    d["_lz_src"] = src                       # strong ref: frozen source
    d["_lz_gen"] = getattr(src, "_gen", 0)
    d["_lz_items"] = None
    d["_lz_tree"] = _TREE_UNSET
    object.__setattr__(new, "_dirty", set())
    object.__setattr__(new, "_root_memo", getattr(src, "_root_memo", None))
    return new


def _fast_class(cls):
    """Cached subclass whose ``copy()`` is the fast clone — so copies of
    fast clones (fork choice copying ``store.block_states`` entries)
    stay C-level through the whole lineage instead of reverting to the
    per-element base walk after the first generation."""
    fc = _fast_cache.get(cls)
    if fc is not None:
        return fc

    def _copy(self):
        _C_FIELD_FAST.add()
        return _fast_sequence_clone(self)

    fc = type(
        "_FastClone_" + cls.__name__, (cls,),
        {"_serving_fast": True, "_serving_base": cls, "copy": _copy},
    )
    _fast_cache[cls] = fc
    return fc


def _fast_sequence_clone(src):
    # Same result as the base copy() — whose element copies are all
    # identity for immutable elements — minus the per-element Python.
    # ``_serving_base`` keeps fast-of-fast from nesting subclasses.
    base = getattr(type(src), "_serving_base", type(src))
    new = object.__new__(_fast_class(base))
    object.__setattr__(new, "_items", list(src._items))
    src._copy_tree_into(new)
    return new


def _clone_value(v, lazy_min):
    if isinstance(v, _SequenceBase):
        cls = type(v)
        if getattr(cls, "_serving_lazy", False):
            # copy() on a lazy instance already does the right thing
            # (virtual -> sibling lazy clone, materialized -> plain);
            # it bumps the lazy counter itself when it stays virtual.
            if v.__dict__["_lz_items"] is None:
                return v.copy()
            _C_FIELD_EAGER.add()
            return v.copy()
        if issubclass(cls.elem_type, _IMMUTABLE_ELEMS):
            _C_FIELD_FAST.add()
            return _fast_sequence_clone(v)
        if len(v._items) >= lazy_min:
            _C_FIELD_LAZY.add()
            return _lazy_sequence_clone(v)
    _C_FIELD_EAGER.add()
    return v.copy()


def clone_state(state: Container) -> Container:
    """Chunk-level clone of an SSZ container (typically a BeaconState).

    Byte-identical to ``state.copy()`` — same serialization, same
    ``hash_tree_root`` — but large composite sequences are cloned
    lazily against the (frozen) source and immutable-element sequences
    share their item lists outright.  An attached ``StateArrays``
    column store is committed and forked exactly as in ``copy()``."""
    store = state.__dict__.get("_state_arrays")
    if store is not None:
        store.commit_for_copy()
    lazy_min = _lazy_min()
    cls = type(state)
    new = object.__new__(cls)
    for f in cls._fields:
        fv = _clone_value(getattr(state, f), lazy_min)
        object.__setattr__(new, f, fv)
        _set_owner(fv, new, f)
    # field clones have identical roots, so the memoized root carries over
    object.__setattr__(new, "_root_cache",
                       object.__getattribute__(state, "_root_cache"))
    if store is not None:
        store.fork(new)
    _C_CLONES.add()
    return new
