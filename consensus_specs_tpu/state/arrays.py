"""Copy-on-write columnar ``StateArrays``: extract once, snapshot
cheaply, replay many.

Three engines used to extract struct-of-arrays views of the same SSZ
beacon state independently — the vectorized epoch engine kept a
root-keyed LRU of registry columns (``ops/epoch_kernels``), the
hash-forest stashed the uint64 columns of its last bulk container-root
build (``utils/ssz/forest``), and proto-array fork choice pulled vote
weights through the epoch engine's cache (``forkchoice/proto_array``).
Each re-keyed by heuristics (roots, weakrefs + generations) and every
state copy or cache eviction paid a fresh O(validators) python pass.

This module promotes the columns to a first-class store attached to the
state object itself:

* **One extraction per state lineage.**  ``of(state)`` returns the
  state's attached :class:`StateArrays`; columns are extracted lazily
  on first access and revalidated *structurally* — every SSZ sequence
  already bumps a mutation generation (``_SequenceBase._gen``) on any
  write through the sequence API, so a column is fresh iff its recorded
  ``(sequence identity, generation)`` still matches.  No root hashing,
  no cache keys, no eviction: the stale-column bug class dies by
  construction.
* **Copy-on-write snapshot/fork.**  :func:`fork_state` copies the SSZ
  state and re-binds the column arrays to the copy without copying
  them.  N concurrent replays (or what-if fork-choice queries) forked
  from one base share one set of arrays; a fork that writes a column
  pays for exactly that column (``registry_writable``), counted in
  ``state_arrays.cow_copies``.
* **One commit per epoch transition.**  Inside a
  :func:`commit_scope` (opened around ``process_epoch`` by
  ``epoch_kernels.install_vectorized_epoch``), engine writes to the balances / inactivity-score columns
  stay in the store and flush back to SSZ chunks once, at scope exit,
  through the chunk-packed ``replace_basic_items(packed=)`` fast path —
  instead of once per sub-transition.  Registry (validator) columns
  commit eagerly: spec helpers outside the engine (sync-committee
  sampling, proposer selection) read effective balances mid-epoch.
* **Shared with merkleization.**  The hash-forest's columnar container
  roots read the store's committed registry columns through
  :func:`peek_registry` (registered as ``forest``'s column provider)
  instead of re-walking the typed views; conversely a forest extraction
  that ran first is adopted by the store (``state_arrays.adoptions``).

``CS_TPU_STATE_ARRAYS=0`` (see ``utils/env_flags.py``) disables the
attached store: ``of`` hands out detached single-use stores, every
access re-extracts, commits are immediate — the slow-but-simple
fallback the differential suites pin against the engine path.

This module is the *host columnar engine* — O(n) numpy passes over
registry columns are its job, and it is the decline target when the
mesh engine's bounded candidate buffers overflow (``mesh.scan_overflow``,
docs/sharding.md).  The speclint N13xx cost pass therefore exempts it
by design (``cost._EXEMPT_RELS``): the O(S)-host-work budget applies to
the ``parallel/`` dispatch paths, not to the fallback that exists
precisely to absorb their declined work.
"""
import weakref
from contextlib import contextmanager

import numpy as np

from consensus_specs_tpu import faults, sanitizer, supervisor
from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.obs.tracing import span
from consensus_specs_tpu.utils import env_flags
from consensus_specs_tpu.utils.ssz import (
    replace_basic_items, sequence_items)
from consensus_specs_tpu.utils.ssz import forest

# ---------------------------------------------------------------------------
# Runtime switch (mirrors epoch_kernels / proto_array)
# ---------------------------------------------------------------------------

_mode = "auto"


def use_arrays() -> None:
    """Force the attached copy-on-write store on."""
    global _mode
    _mode = "on"


def use_fallback() -> None:
    """Force detached single-use stores (the per-call extraction path)."""
    global _mode
    _mode = "off"


def use_auto() -> None:
    """Default policy: on unless ``CS_TPU_STATE_ARRAYS=0``."""
    global _mode
    _mode = "auto"


def enabled() -> bool:
    if _mode == "on":
        return True
    if _mode == "off":
        return False
    return env_flags.switch("CS_TPU_STATE_ARRAYS")


def backend_name() -> str:
    return "state_arrays" if enabled() else "fallback"


# ---------------------------------------------------------------------------
# Metrics (pre-bound series, speclint O5xx hot-path rule)
# ---------------------------------------------------------------------------

_C_HIT = obs_registry.counter("cache.hit").labels(cache="state_arrays")
_C_MISS = obs_registry.counter("cache.miss").labels(cache="state_arrays")
# python-pass column extractions, by column family — the census the
# bench smoke counter-asserts ("no engine re-extracts within an epoch")
_C_X_REG = obs_registry.counter("state_arrays.extracts").labels(
    column="registry")
_C_X_BAL = obs_registry.counter("state_arrays.extracts").labels(
    column="balances")
_C_X_INACT = obs_registry.counter("state_arrays.extracts").labels(
    column="inactivity_scores")
_C_X_PART = obs_registry.counter("state_arrays.extracts").labels(
    column="participation")
# registry extractions satisfied for free from the hash-forest's bulk
# container-root column stash (no python pass)
_C_ADOPTIONS = obs_registry.counter("state_arrays.adoptions").labels()
_C_COMMITS = obs_registry.counter("state_arrays.commits").labels()
_C_COW = obs_registry.counter("state_arrays.cow_copies").labels()
_C_FORKS = obs_registry.counter("state_arrays.forks").labels()
# chunk-packed-commit fallbacks: the per-index write loop taken because
# an injected fault (consensus_specs_tpu/faults.py) failed the batched
# committer.  No organic series: the committer has no guard of its own.
_FALLBACKS = {
    "injected": obs_registry.counter(
        "state_arrays.fallbacks").labels(reason="injected"),
    "deadline": obs_registry.counter(
        "state_arrays.fallbacks").labels(reason="deadline"),
}


# ---------------------------------------------------------------------------
# Column extraction / write-back primitives
# ---------------------------------------------------------------------------

VALIDATOR_DTYPE = np.dtype([
    ("eff", "<u8"),    # effective_balance
    ("aee", "<u8"),    # activation_eligibility_epoch
    ("act", "<u8"),    # activation_epoch
    ("ext", "<u8"),    # exit_epoch
    ("wd", "<u8"),     # withdrawable_epoch
    ("sl", "?"),       # slashed
])

# SSZ Validator field name -> VALIDATOR_DTYPE key
REGISTRY_FIELDS = (
    ("effective_balance", "eff"), ("activation_eligibility_epoch", "aee"),
    ("activation_epoch", "act"), ("exit_epoch", "ext"),
    ("withdrawable_epoch", "wd"), ("slashed", "sl"))


def u64_column(seq) -> np.ndarray:
    """One uint64 column from a basic-element List/Vector."""
    items = sequence_items(seq)
    return np.fromiter(items, dtype=np.uint64, count=len(items))


def _write_u64_list(seq, elem_type, old, new) -> None:
    """Commit a uint64 column back into its SSZ list, matching the spec
    loop's per-index writes bit-for-bit but without its per-index python
    cost.  Few changes -> targeted ``__setitem__`` (keeps the incremental
    chunk tree); registry-wide changes -> wholesale item swap, building
    the element objects through a value-dedup table (epoch deltas are
    highly repetitive: equal-stake validators earn equal rewards) and
    committing chunk-level: the 32-byte leaf chunks are packed straight
    from the column (``new.astype('<u8').tobytes()``) and bulk-fed to
    the tree, so the commit materializes zero per-chunk python work and
    re-hashes through the batched layer path."""
    changed = np.nonzero(old != new)[0]
    if changed.size == 0:
        return
    if changed.size <= max(64, len(old) // 64):
        _write_u64_list_loop(seq, elem_type, old, new)
        return
    vals, inv = np.unique(new, return_inverse=True)
    if vals.size * 4 <= new.size:
        pool = [elem_type(int(v)) for v in vals.tolist()]
        items = [pool[i] for i in inv.tolist()]
    else:
        # int.__new__ skips BasicValue's range re-validation; the values
        # come out of a uint64 array, so the range holds by construction
        items = [int.__new__(elem_type, v) for v in new.tolist()]
    # cooperative deadline boundary: the object-building stage above is
    # the python-heavy part and nothing has been written yet — an armed
    # budget (supervisor.deadline_scope in commit) aborts here into the
    # counted spec-shaped loop write instead of past the point of
    # no return
    supervisor.deadline_check()
    replace_basic_items(seq, items, packed=new.astype("<u8").tobytes())


def _write_u64_list_loop(seq, elem_type, old, new) -> None:
    """The spec-shaped committer: targeted per-index ``__setitem__``
    writes.  Doubles as :func:`_write_u64_list`'s small-diff branch
    (one shared loop, so the two paths cannot drift) and as the
    graceful-degradation leg an injected commit fault forces — the
    path the adversarial harness proves byte-identical."""
    for i in np.nonzero(old != new)[0].tolist():
        seq[i] = elem_type(int(new[i]))


def _gen_of(seq) -> int:
    return getattr(seq, "_gen", 0)


def _extract_registry(seq) -> np.ndarray:
    """The validator registry as one structured array.  First choice:
    adopt the uint64 columns the hash-forest's last columnar root build
    stashed (generation-validated, zero python passes); fallback: a
    single ``np.fromiter`` pass over the typed views."""
    items = sequence_items(seq)
    n = len(items)
    shared = forest.peek_columns(seq)
    if shared is not None and all(f in shared for f, _ in REGISTRY_FIELDS):
        cols = np.empty(n, dtype=VALIDATOR_DTYPE)
        for fname, key in REGISTRY_FIELDS:
            if key == "sl":
                cols[key] = shared[fname] != 0
            else:
                cols[key] = shared[fname]
        _C_ADOPTIONS.add()
        return cols
    cols = np.fromiter(
        ((v.effective_balance, v.activation_eligibility_epoch,
          v.activation_epoch, v.exit_epoch, v.withdrawable_epoch,
          bool(v.slashed)) for v in items),
        dtype=VALIDATOR_DTYPE, count=n)
    _C_X_REG.add()
    return cols


def _extract_u64(counter):
    def extract(seq):
        col = u64_column(seq)
        counter.add()
        return col
    return extract


def _extract_u8(seq) -> np.ndarray:
    items = sequence_items(seq)
    col = np.fromiter(items, dtype=np.uint8, count=len(items))
    _C_X_PART.add()
    return col


# ---------------------------------------------------------------------------
# The store
# ---------------------------------------------------------------------------

class _Cell:
    """One column (or column group) of one SSZ sequence.

    ``base`` is the committed view — it always equals the SSZ content
    as long as ``(seq identity, gen)`` still matches — and is never
    mutated in place, so forks may share it freely.  ``data`` is the
    current value: ``data is base`` means clean; anything else is a
    pending engine write awaiting :meth:`StateArrays.commit`.

    ``shard`` is the mesh engine's device placement for this column
    (``parallel/mesh_state.py``): ``(host_array, placed, epoch)`` where
    ``placed`` is the column padded and ``device_put`` across the
    validator mesh and ``epoch`` is the mesh placement epoch it was
    made under.  Validity is by identity — the placement serves reads
    only while ``shard[0] is cell.data`` and the epoch still matches
    (a device loss bumps the global epoch, retiring every placement on
    the lost mesh at once) — so a kernel write (a new ``data`` array)
    retires it without bookkeeping, and a copy-on-write fork that
    shares ``data`` shares the placement too: N replays forked from one
    base pay ONE host->device transfer per column, and committing a
    scope (``base = data``) never moves data between devices.
    """

    __slots__ = ("data", "base", "seq_ref", "gen", "shard", "__weakref__")

    def __init__(self, data, seq):
        self.data = data
        self.base = data
        self.seq_ref = weakref.ref(seq)
        self.gen = _gen_of(seq)
        self.shard = None


# (name, state field, extractor); participation columns are altair+.
_COLUMNS = {
    "registry": ("validators", _extract_registry),
    "balances": ("balances", _extract_u64(_C_X_BAL)),
    "inactivity_scores": ("inactivity_scores", _extract_u64(_C_X_INACT)),
    "participation_previous": ("previous_epoch_participation", _extract_u8),
    "participation_current": ("current_epoch_participation", _extract_u8),
}

# columns whose engine writes may sit in the store across sub-transitions
# of one commit_scope (registry commits are always eager: spec helpers
# outside the engine read effective balances mid-epoch)
_DEFERRABLE = ("balances", "inactivity_scores")

_ATTR = "_state_arrays"


class StateArrays:
    """Columnar view of one beacon state (see module docstring)."""

    __slots__ = ("_state_ref", "_cells", "_deferred", "__weakref__")

    def __init__(self, state):
        self._state_ref = weakref.ref(state)
        self._cells = {}
        self._deferred = False

    # -- plumbing -----------------------------------------------------------

    def _state(self):
        state = self._state_ref()
        if state is None:
            raise RuntimeError("StateArrays outlived its state")
        return state

    def _seq(self, name):
        return object.__getattribute__(self._state(), _COLUMNS[name][0])

    def _cell(self, name) -> _Cell:
        """The validated cell for ``name``: structurally fresh (same
        sequence object, same mutation generation) or re-extracted."""
        seq = self._seq(name)
        cell = self._cells.get(name)
        if cell is not None and cell.seq_ref() is seq \
                and cell.gen == _gen_of(seq):
            _C_HIT.add()
            return cell
        if cell is not None and name in _DEFERRABLE \
                and cell.data is not cell.base:
            # the SSZ list was written directly while an engine column
            # write was pending — re-extracting would silently drop the
            # engine write.  Same fail-loud contract as commit(); the
            # registry cell is exempt because its write protocol
            # (registry_writable -> matching SSZ writes ->
            # mark_registry_committed) legitimately passes through a
            # stale-generation window.  Under CS_TPU_SANITIZER the
            # raise names the speclint twin (E1201).
            raise sanitizer.effect_error(
                "E1201",
                f"state_arrays: {name} mutated through the SSZ API "
                f"while a deferred engine write was pending"
                + _pending_detail(self))
        _C_MISS.add()
        cell = _Cell(_COLUMNS[name][1](seq), seq)
        self._cells[name] = cell
        if name == "registry":
            _bind_registry(seq, cell)
        return cell

    # -- registry (structured VALIDATOR_DTYPE array) ------------------------

    def registry(self) -> np.ndarray:
        """Read-only structured registry columns (callers must never
        mutate the returned array; writes go through
        :meth:`registry_writable`)."""
        return self._cell("registry").data

    def registry_writable(self) -> np.ndarray:
        """A private registry array the engine may mutate in place —
        copy-on-write: shared/clean cells are copied here, exactly
        once.  The engine must apply the same changes to the SSZ state
        and then call :meth:`mark_registry_committed`."""
        cell = self._cell("registry")
        if cell.data is cell.base:
            cell.data = cell.base.copy()
            _C_COW.add()
        return cell.data

    def mark_registry_committed(self) -> None:
        """Declare the writable registry columns and the SSZ registry
        identical again (the engine just applied matching per-index
        writes through the sequence API)."""
        cell = self._cells.get("registry")
        if cell is None:
            return
        seq = self._seq("registry")
        if cell.seq_ref() is not seq:
            return
        cell.base = cell.data
        cell.gen = _gen_of(seq)

    # -- uint64 / participation columns -------------------------------------

    def balances(self) -> np.ndarray:
        """Current balances column — includes writes still pending in a
        commit scope (read-only contract)."""
        return self._cell("balances").data

    def set_balances(self, new: np.ndarray) -> None:
        self._set("balances", new)

    def inactivity_scores(self) -> np.ndarray:
        return self._cell("inactivity_scores").data

    def set_inactivity_scores(self, new: np.ndarray) -> None:
        self._set("inactivity_scores", new)

    def participation(self, which: str) -> np.ndarray:
        """uint8 participation-flag column; ``which`` is ``"previous"``
        or ``"current"`` (altair+ states only)."""
        return self._cell(f"participation_{which}").data

    def _set(self, name, new) -> None:
        cell = self._cell(name)
        if new.dtype != np.uint64 or new.shape != cell.base.shape:
            raise ValueError(f"state_arrays.{name}: column shape/dtype "
                             f"mismatch ({new.dtype}, {new.shape})")
        cell.data = new
        if not self._deferred:
            self.commit()
        else:
            sanitizer.deferred_write(self, name)

    # -- commit / discard ---------------------------------------------------

    def commit(self) -> None:
        """Write every pending deferrable column back to its SSZ list
        (chunk-packed, one batched tree rebuild per column) and re-stamp
        the cells as committed."""
        wrote = False
        for name in _DEFERRABLE:
            cell = self._cells.get(name)
            if cell is None or cell.data is cell.base:
                continue
            seq = self._seq(name)
            if cell.seq_ref() is not seq or cell.gen != _gen_of(seq):
                # the SSZ list was written directly while an engine
                # column write was pending — committing would clobber
                # one of the two.  No wired path does this; fail loud
                # (naming the speclint twin E1201 when the sanitizer
                # is armed).
                raise sanitizer.effect_error(
                    "E1201",
                    f"state_arrays: {name} mutated through the SSZ API "
                    f"while a deferred engine write was pending"
                    + _pending_detail(self))
            if not wrote:
                _C_COMMITS.add()
                wrote = True
            with span("state_arrays.commit"):
                site = "state_arrays.commit"
                fast = supervisor.admit(site)
                if fast:
                    try:
                        faults.check(site)
                        with supervisor.deadline_scope(site):
                            data = cell.data
                            if faults.corrupt_armed(site):
                                # silent-corruption injection (sentinel-
                                # audit test vector): one flipped bit in
                                # the chunk-packed write; cell.data stays
                                # true, so the read-back audit can see it
                                data = data.copy()
                                if data.size:
                                    data[0] ^= np.uint64(1)
                            _write_u64_list(seq, type(seq).elem_type,
                                            cell.base, data)
                    except (faults.InjectedFault,
                            supervisor.DeadlineExceeded) as exc:
                        faults.count_fallback(_FALLBACKS, exc,
                                              organic="injected", site=site)
                        fast = False
                if not fast:
                    _write_u64_list_loop(seq, type(seq).elem_type,
                                         cell.base, cell.data)
                elif supervisor.audit_due(site):
                    # sentinel audit: re-extract the committed column
                    # and compare against the engine's pending data; on
                    # a mismatch the site is quarantined and the column
                    # repaired through the spec-shaped targeted writes
                    back = u64_column(seq)
                    ok = bool(np.array_equal(back, cell.data))
                    supervisor.audit_result(
                        site, ok, f"chunk-packed commit of {name} read "
                        "back differently than the pending column")
                    if not ok:
                        _write_u64_list_loop(seq, type(seq).elem_type,
                                             back, cell.data)
                else:
                    supervisor.note_success(site)
                cell.base = cell.data
                cell.gen = _gen_of(seq)

    def commit_for_copy(self) -> None:
        """``Container.copy``'s pre-snapshot commit: exactly
        :meth:`commit`, plus the sanitizer's E1202 shadow check — a
        copy/fork with pending deferred writes inside an open commit
        scope is a LEGAL early commit (the child must see the flushed
        columns), but the one-commit-per-epoch contract silently
        degraded, so the armed sanitizer counts it."""
        if sanitizer.enabled():
            sanitizer.fork_event(self, self._deferred and any(
                c is not None and c.data is not c.base
                for c in (self._cells.get(n) for n in _DEFERRABLE)))
        self.commit()

    def discard_pending(self) -> None:
        """Drop uncommitted engine writes (the enclosing transition
        failed; the SSZ state is authoritative)."""
        for name in _DEFERRABLE:
            cell = self._cells.get(name)
            if cell is not None:
                cell.data = cell.base

    # -- snapshot / fork ----------------------------------------------------

    def fork(self, new_state) -> "StateArrays":
        """Bind this store's columns to ``new_state`` (an ``ssz.copy``
        of the owner) without copying them: base arrays are immutable
        by contract, so both lineages share until one writes.  Pending
        writes are committed first so the copied SSZ content matches
        the shared columns.  Only cells still structurally valid
        against the parent's sequences come along — a stale cell (the
        sequence mutated since extraction) is dropped, NOT rebound:
        stamping it with the child's fresh generation would launder
        stale data into a "valid" column."""
        self.commit()
        other = StateArrays(new_state)
        if not enabled():
            # the store was disabled after this lineage attached its
            # columns: share NOTHING with the copy — no cells, no
            # forest provider binding, no attach.  The copy behaves
            # like a plain ``ssz`` copy, which the store-off
            # differential-oracle legs rely on (shared columns would
            # let a store bug cancel out of both sides of a
            # forked-vs-independent root comparison).
            return other
        parent = self._state()
        for name, cell in self._cells.items():
            field = _COLUMNS[name][0]
            pseq = object.__getattribute__(parent, field)
            if cell.seq_ref() is not pseq or cell.gen != _gen_of(pseq):
                continue
            seq = object.__getattribute__(new_state, field)
            ncell = _Cell(cell.data, seq)
            # the mesh device placement rides along with the shared
            # column: a forked replay dispatches against the SAME
            # device arrays until it writes the column (identity check
            # in parallel/mesh_state.sharded_cell retires it then)
            ncell.shard = cell.shard
            other._cells[name] = ncell
            if name == "registry":
                _bind_registry(seq, ncell)
        object.__setattr__(new_state, _ATTR, other)
        _C_FORKS.add()
        return other


# ---------------------------------------------------------------------------
# Module-level surface
# ---------------------------------------------------------------------------

def _pending_detail(store) -> str:
    """The armed sanitizer's scope-ledger view of which deferred
    columns an E1201 violation would clobber — empty when disarmed or
    untracked."""
    pending = sanitizer.pending_columns(store)
    return f" (would clobber deferred: {', '.join(pending)})" \
        if pending else ""


def of(state) -> StateArrays:
    """The state's attached store (created on first use).  With the
    engine disabled every call returns a detached single-use store:
    per-call extraction, immediate commits, no sharing."""
    if not enabled():
        return StateArrays(state)
    store = state.__dict__.get(_ATTR)
    if store is None or store._state_ref() is not state:
        store = StateArrays(state)
        object.__setattr__(state, _ATTR, store)
    return store


def registry_of(state) -> np.ndarray:
    """Shorthand for ``of(state).registry()`` — the one sanctioned way
    for engine code to read validator registry columns."""
    return of(state).registry()


def flush(state) -> None:
    """Commit any pending deferred writes of ``state``'s attached store
    (no-op when none): every spec-loop fallback calls this before
    reading SSZ, so a half-deferred epoch can never expose stale
    balances to non-engine code."""
    d = getattr(state, "__dict__", None)
    store = d.get(_ATTR) if d is not None else None
    if store is not None and store._state_ref() is state:
        store.commit()


@contextmanager
def commit_scope(state):
    """Defer the store's balance-family commits across the enclosed
    epoch transition: sub-transitions write columns, SSZ sees ONE
    chunk-packed commit per column at scope exit.  Reentrant; a no-op
    when the engine is disabled.  On an exception the pending writes
    are discarded (exception-as-invalidity: the caller abandons the
    state)."""
    if not enabled():
        yield
        return
    store = of(state)
    if store._deferred:
        yield
        return
    store._deferred = True
    sanitizer.scope_opened(store)
    try:
        yield
    except BaseException:
        store._deferred = False
        store.discard_pending()
        sanitizer.scope_closed(store)
        raise
    store._deferred = False
    store.commit()
    sanitizer.scope_closed(store)


def fork_state(state):
    """``ssz`` state copy + column fork in one step: the returned state
    carries a store sharing this state's column arrays copy-on-write.
    The cheap way to run N concurrent replays off one base snapshot.

    With the store enabled, every plain ``state.copy()`` of a
    store-carrying state does this too (``Container.copy`` flushes
    pending writes before the field snapshot and forks the store after
    it) — this helper just guarantees a store is attached first.  With
    the store disabled it degrades to a plain ``ssz`` copy (detached
    stores have no cells to share, and counting a column-less fork
    would skew the telemetry)."""
    from consensus_specs_tpu.utils.ssz import copy as ssz_copy
    if enabled():
        of(state)               # attach; the copy hook forks it
    return ssz_copy(state)


# ---------------------------------------------------------------------------
# Column sharing with the hash-forest (utils/ssz/forest.py)
# ---------------------------------------------------------------------------

_REG_CELL_ATTR = "_sa_registry_cell"


def _bind_registry(seq, cell) -> None:
    """Backpointer for :func:`peek_registry`: the sequence knows its
    (weakly-held) registry cell, so the forest's columnar root build
    finds the columns without knowing about states or stores."""
    setattr(seq, _REG_CELL_ATTR, weakref.ref(cell))


def peek_registry(seq):
    """The committed registry columns bound to ``seq`` as
    ``{ssz field name: uint64 array}`` — or None when the cell is gone,
    stale, or belongs to another sequence.  Registered as the forest's
    column provider: bulk container-root builds read these instead of
    re-walking the typed views."""
    ref = getattr(seq, _REG_CELL_ATTR, None)
    if ref is None:
        return None
    cell = ref()
    if cell is None or cell.seq_ref() is not seq \
            or cell.gen != _gen_of(seq):
        return None
    base = cell.base
    out = {}
    for fname, key in REGISTRY_FIELDS:
        col = base[key]
        out[fname] = col.astype(np.uint64) if key == "sl" else col
    return out


forest.set_column_provider(peek_registry)
