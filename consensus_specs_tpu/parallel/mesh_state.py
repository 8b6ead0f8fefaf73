"""Mesh-sharded state columns: partition the ``StateArrays`` validator
axis across a 1-D device mesh.

PR 7 promoted the beacon state's hot columns (registry structured
columns, balances, inactivity scores, participation flags) to ONE
copy-on-write struct-of-arrays store per state lineage — explicitly
"one array set to shard".  This module shards that array set: columns
are padded to a multiple of the device count and ``device_put`` with a
``NamedSharding`` over the 1-D ``validators`` mesh, so the SPMD epoch
programs (``mesh_epoch.py``) and the leaf-span merkleization
(``mesh_merkle.py``) consume per-device shards without any per-dispatch
re-partitioning.

Placement lifecycle — stable across copy-on-write forks and commit
scopes:

* a placement is cached on the store cell (``state/arrays._Cell.shard``)
  keyed by the host array's *identity*: valid while ``shard[0] is
  cell.data``;
* a copy-on-write fork shares ``cell.data`` and therefore the
  placement — N replays forked from one base pay ONE host->device
  transfer per column (``mesh.placements`` counts them);
* a kernel write replaces ``cell.data`` with a fresh array, which
  retires the placement by construction — no invalidation hooks, the
  same no-stale-by-construction argument as the store's generation
  revalidation;
* committing a scope only re-stamps ``base = data``; the placed shards
  never move;
* in-place registry mutation batches are safe under the identity key:
  ``registry_writable`` COPIES whenever the cell is committed, so
  every write batch starts a fresh identity, and the engines never
  read the mesh inside a batch — reads land either before the
  copy-on-write (old identity, old data: consistent) or after the
  batch's paired SSZ writes complete (new identity: re-placed).

Switch: ``CS_TPU_MESH`` (live ``env_flags.switch``), additionally gated
on a multi-device host — a 1-device mesh is pure overhead, so
``enabled()`` is False there no matter the variable.  Engagement floors
(``CS_TPU_MESH_MIN`` validators, ``CS_TPU_MESH_MERKLE_MIN`` leaf
chunks) keep the engine out of small registries, where host numpy wins;
``use_mesh()`` (tests, benches) overrides the floors but not the
device-count gate.

uint64 columns need 64-bit lanes: every placement and program dispatch
runs inside ``jax.enable_x64`` so the rest of the process
(the u32-limb BLS/SHA kernels) keeps the default dtype rules.

Device-loss recovery (docs/recovery.md): a device dropping out of the
``validators`` mesh mid-dispatch surfaces as :class:`DeviceLoss` (the
fault layer injects it via ``faults.loss_armed``; a real XLA device
failure would be translated by the same handler).  The handler calls
:func:`lose_device`, which shrinks the active device set and bumps the
global *placement epoch* — every cached ``_Cell.shard`` placement
carries the epoch it was placed under, so ALL placements on the old
mesh retire at once without walking any store — then the dispatch
rebuilds :func:`build_mesh` over the survivors and re-shards
elastically.  The two-device gate and the engagement floors keep
applying: losing down to one device degrades to the single-device
engines, byte-identical.
"""
import numpy as np

from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.utils import env_flags

AXIS = "validators"


class DeviceLoss(Exception):
    """A device dropped out of the mesh mid-dispatch.  A fallback-class
    exception: the mesh dispatch handlers catch it, re-shard over the
    survivors and book a counted ``reason=device_loss`` fallback."""

    def __init__(self, site: str):
        super().__init__(f"{site}: mesh device lost mid-dispatch")
        self.site = site

# Engagement floors: below these the partition/transfer overhead beats
# any per-shard win.  Live knobs (read per call through env_flags.knob)
# so a CI leg or bench can force engagement at toy sizes.
DEFAULT_MESH_MIN = 1 << 16           # validators, epoch programs
DEFAULT_MERKLE_MIN = 1 << 14         # leaf chunks, merkle span builds

_mode = "auto"


def use_mesh() -> None:
    """Force the mesh engine on (floors bypassed; the multi-device gate
    still applies — there is nothing to shard over on one device)."""
    global _mode
    _mode = "on"


def use_fallback() -> None:
    """Force the single-device engines."""
    global _mode
    _mode = "off"


def use_auto() -> None:
    """Default policy: on unless ``CS_TPU_MESH=0``, multi-device hosts
    only, engagement floors applied."""
    global _mode
    _mode = "auto"


_DEVICE_COUNT = None

# device-loss state: how many devices (from the END of jax.devices(),
# deterministically) are currently lost, and the placement epoch every
# cached cell placement is stamped with — bumping it retires every
# placement on the old mesh at once (no store walking)
_LOST = 0
_PLACEMENT_EPOCH = 0


def device_count() -> int:
    """SURVIVING addressable device count, memoized.  A process that
    never imported jax answers 0 WITHOUT importing it: the mesh gate
    sits on every epoch dispatch and every full tree build, and a
    pure-host replay (spec loops, numpy engines, benches with BLS off)
    must not pay a jax backend initialization just to learn there is
    nothing to shard over."""
    global _DEVICE_COUNT
    if _DEVICE_COUNT is None:
        import sys
        if "jax" not in sys.modules:
            return 0        # not cached: jax may be imported later
        import jax
        _DEVICE_COUNT = len(jax.devices())
    return max(0, _DEVICE_COUNT - _LOST)


def active_devices():
    """The surviving device tuple the mesh builds over."""
    import jax
    devices = tuple(jax.devices())
    return devices[:len(devices) - _LOST] if _LOST else devices


def placement_epoch() -> int:
    return _PLACEMENT_EPOCH


def lose_device(site: str = "mesh") -> int:
    """Drop one device from the active set (the last, deterministically)
    and retire EVERY cached placement by bumping the placement epoch.
    Returns the surviving device count.  Idempotent bookkeeping: the
    mesh cache keeps old meshes for their key identity, but
    :func:`build_mesh` with default devices only ever hands out the
    survivor mesh from here on."""
    global _LOST, _PLACEMENT_EPOCH
    total = device_count()
    if total > 0:
        _LOST += 1
    _PLACEMENT_EPOCH += 1
    series = _C_DEVICE_LOSSES.get(site)
    if series is None:      # cold resolution only for unknown sites
        series = obs_registry.counter("mesh.device_losses") \
            .labels(site=site)
    series.add()
    survivors = device_count()
    _G_SHARDS.set(survivors)
    return survivors


def restore_devices() -> None:
    """Forget all device losses (test/harness lifecycle); placements
    made against the degraded mesh retire via the epoch bump."""
    global _LOST, _PLACEMENT_EPOCH
    if _LOST:
        _LOST = 0
        _PLACEMENT_EPOCH += 1
    _G_SHARDS.set(device_count())


def enabled() -> bool:
    if _mode == "off":
        return False
    if device_count() < 2:
        return False
    if _mode == "on":
        return True
    return env_flags.switch("CS_TPU_MESH")


def backend_name() -> str:
    return "mesh" if enabled() else "fallback"


def _floor(name: str, default: int) -> int:
    raw = env_flags.knob(name)
    try:
        return int(raw)
    except (TypeError, ValueError):
        return default


def engaged(n_validators: int) -> bool:
    """Whether the SPMD epoch programs take a registry of this size."""
    if not enabled():
        return False
    if _mode == "on":
        return n_validators >= device_count()
    return n_validators >= max(device_count(),
                               _floor("CS_TPU_MESH_MIN", DEFAULT_MESH_MIN))


def merkle_engaged(n_chunks: int) -> bool:
    """Whether leaf-span merkleization takes a tree of this many leaf
    chunks (``mesh_merkle.build_levels``)."""
    if not enabled():
        return False
    if _mode == "on":
        return n_chunks >= 2 * device_count()
    return n_chunks >= max(2 * device_count(),
                           _floor("CS_TPU_MESH_MERKLE_MIN",
                                  DEFAULT_MERKLE_MIN))


# ---------------------------------------------------------------------------
# Metrics (pre-bound series, speclint O5xx hot-path rule)
# ---------------------------------------------------------------------------

_C_PLACE = {
    name: obs_registry.counter("mesh.placements").labels(column=name)
    for name in ("registry", "balances", "inactivity_scores",
                 "participation", "scalars", "leaves")}
_G_SHARDS = obs_registry.gauge("mesh.shards").labels()
_C_DEVICE_LOSSES = {
    site: obs_registry.counter("mesh.device_losses").labels(site=site)
    for site in ("mesh.epoch", "mesh.merkle")}


# ---------------------------------------------------------------------------
# Mesh construction (fold of the sharded_verify helpers: shape derived
# from jax.devices(), memoized per axis/device tuple)
# ---------------------------------------------------------------------------

# speclint: cost: bounded: keyed per (axis, surviving-device tuple)
_MESH_CACHE = {}


def build_mesh(axis: str = AXIS, devices=None):
    """Memoized 1-D ``jax.sharding.Mesh`` over ``devices`` (default:
    every SURVIVING addressable device — the shape is derived, never
    hardcoded, and a device loss shrinks it elastically).  Rebuilding a
    mesh per call would defeat jit's identity-keyed program cache, the
    same rationale as ``sharded_verify._sharded_msm_for``."""
    from jax.sharding import Mesh
    devices = tuple(devices) if devices is not None else active_devices()
    key = (axis, devices)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = Mesh(np.array(devices), (axis,))
        _MESH_CACHE[key] = mesh
        if axis == AXIS:
            _G_SHARDS.set(len(devices))
    return mesh


def n_shards() -> int:
    return device_count()


def pad_amount(n: int, shards: int = None) -> int:
    """Zero-rows appended so the leading axis divides across the mesh
    (uneven registries shard too — the pad lanes are masked out of every
    reduction and sliced off every result)."""
    if shards is None:
        shards = n_shards()
    return (-n) % shards


def x64():
    """The scoped 64-bit-lane context every mesh placement/dispatch runs
    under (module docstring)."""
    import jax
    return jax.enable_x64(True)


def place(host: np.ndarray, mesh, pad_value=0):
    """Pad ``host`` along axis 0 to the mesh width and ``device_put``
    with a 1-D ``NamedSharding``.  Caller holds the x64 scope."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    pad = pad_amount(host.shape[0], mesh.shape[AXIS])
    if pad:
        padding = np.full((pad,) + host.shape[1:], pad_value,
                          dtype=host.dtype)
        host = np.concatenate([host, padding])
    return jax.device_put(host, NamedSharding(mesh, P(AXIS)))


def replicate(host: np.ndarray, mesh):
    """A small operand (the scalar vector) replicated on every device.
    Caller holds the x64 scope."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    _C_PLACE["scalars"].add()
    return jax.device_put(host, NamedSharding(mesh, P()))


# ---------------------------------------------------------------------------
# Cell-anchored placements (state/arrays.py integration)
# ---------------------------------------------------------------------------

# registry structured columns are placed as one device array per field
REGISTRY_U64_FIELDS = ("eff", "aee", "act", "ext", "wd")


def sharded_cell(sa, name: str, mesh):
    """The device placement of one store column, cached on the cell and
    valid while the cell's current array is the one that was placed
    (identity check — see module docstring) AND the placement epoch
    still matches (a device loss bumps the epoch, retiring every
    placement on the old mesh at once).  Returns the placed device
    array (or ``{field: array}`` dict for the structured registry)."""
    cell = sa._cell(name)
    sh = cell.shard
    if sh is not None and sh[0] is cell.data \
            and sh[2] == _PLACEMENT_EPOCH:
        return sh[1]
    host = cell.data
    with x64():
        if name == "registry":
            placed = {f: place(np.ascontiguousarray(host[f]), mesh)
                      for f in REGISTRY_U64_FIELDS}
            placed["sl"] = place(np.ascontiguousarray(host["sl"]), mesh,
                                  pad_value=False)
            _C_PLACE["registry"].add()
        else:
            placed = place(host, mesh)
            # participation_previous / participation_current share one
            # series; the other column names are series keys directly
            _C_PLACE.get(name, _C_PLACE["participation"]).add()
    cell.shard = (host, placed, _PLACEMENT_EPOCH)
    return placed


def unshard(device_array, n: int) -> np.ndarray:
    """Back to host numpy, pad rows sliced off."""
    return np.asarray(device_array)[:n]
