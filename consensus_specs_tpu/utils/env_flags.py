"""Environment tier flags, importable without pulling in jax or the fork
registry (test modules read these at collection time)."""
import os

# Heavy crypto tier gate (jit-compile-bound tests; ``make test-crypto``)
HEAVY = os.environ.get("CS_TPU_HEAVY") == "1"


# ---------------------------------------------------------------------------
# Unified engine-switch accessor
# ---------------------------------------------------------------------------
# Every accelerated engine hangs off one boolean ``CS_TPU_*`` variable
# with the same contract: on unless the variable is exactly ``"0"``.
# Before PR 9 the live-re-read behavior was implemented per engine
# (``bls.rlc_enabled``, ``proto_array.enabled``, ...) with slightly
# different fallbacks, and some consumers latched the import-time
# module constant instead.  :func:`switch` is the one source of truth:
#
# * variable present in the environment -> live re-read (a CI leg or
#   the supervisor can flip an engine after import and every dispatch
#   sees it on the next call);
# * variable absent -> the cached import-time default, re-snapshotted
#   only by an explicit :func:`refresh` (so deleting the variable
#   mid-process restores the state the process STARTED with instead of
#   whatever the last override happened to be).
#
# The per-call cost is one ``os.environ`` lookup — the same price the
# engines already paid individually.

ENGINE_SWITCHES = (
    "CS_TPU_VECTORIZED_EPOCH",
    "CS_TPU_PROTO_ARRAY",
    "CS_TPU_STATE_ARRAYS",
    "CS_TPU_BLS_RLC",
    "CS_TPU_HASH_FOREST",
    "CS_TPU_SUPERVISOR",
    "CS_TPU_DAS",
    "CS_TPU_MESH",
    "CS_TPU_CHECKPOINT",
    "CS_TPU_SERVING",
    # observability, not an engine (no consensus result depends on it),
    # but it shares the switch contract: the flight recorder
    # (``obs/flight.py``) is on unless CS_TPU_FLIGHT=0.  Ring size is a
    # knob, CS_TPU_FLIGHT_SIZE (default 1024 slots per thread).
    "CS_TPU_FLIGHT",
)

_SWITCH_DEFAULTS = {}


def _snapshot_switches() -> None:
    for name in ENGINE_SWITCHES:
        _SWITCH_DEFAULTS[name] = os.environ.get(name) != "0"


_snapshot_switches()


def switch(name: str) -> bool:
    """Live boolean engine switch (see the block comment above)."""
    raw = os.environ.get(name)
    if raw is None:
        return _SWITCH_DEFAULTS.get(name, True)
    return raw != "0"


def refresh() -> None:
    """Explicitly invalidate the cached import-time defaults (rarely
    needed: only when a harness wants an *unset* variable to mean "the
    environment as it is now" rather than "as it was at import")."""
    _snapshot_switches()


def knob(name: str, default=None):
    """Raw string knob: the sanctioned access point for non-switch
    ``CS_TPU_*`` environment variables.  Engine code must not read
    ``os.environ`` directly (speclint D1003): routing every read
    through this module keeps the full set of environment dependencies
    declarable and auditable in one place — ambient state a consensus
    result may depend on is exactly what the determinism pass
    exists to fence."""
    return os.environ.get(name, default)


def _int_env(name):
    """Optional integer env knob: None when unset or non-numeric."""
    raw = os.environ.get(name, "")
    try:
        return int(raw)
    except ValueError:
        return None


# Merkleization batching floor.  When set, overrides BOTH batching
# thresholds in ``utils/ssz/merkle.py``: the kernel-layer threshold
# (``_BATCH_THRESHOLD``, default 256 — 64-byte inputs above which a full
# layer is dispatched to the batched JAX hasher instead of native C /
# hashlib) and the dirty-pair batching floor (``_PAIR_BATCH_MIN``,
# default 32 — dirty sibling pairs per tree level above which the
# incremental engine gathers the level into one batched dispatch instead
# of a per-pair hashlib loop).  ``CS_TPU_MERKLE_BATCH_MIN=1`` forces the
# batched paths everywhere; a huge value forces the scalar paths.
MERKLE_BATCH_MIN = _int_env("CS_TPU_MERKLE_BATCH_MIN")

# Hash-forest batch scope kill switch: ``CS_TPU_HASH_FOREST=0`` turns
# ``utils/ssz/forest.py`` scopes into no-ops (every tree flushes alone)
# and disables the columnar bulk container-root path.
HASH_FOREST = os.environ.get("CS_TPU_HASH_FOREST") != "0"

# Telemetry span gates (``consensus_specs_tpu/obs``).  PROFILE turns on
# hierarchical tracing spans (wall-clock span tree + flat aggregates,
# ``obs.tracing``); TRACE additionally
# attaches per-span counter deltas (a registry-wide counter diff on
# every span entry/exit — more detail, more overhead) and implies
# PROFILE.  Both default OFF: the disabled span path is a single
# module-global read.  Metric *counters* are not gated — the
# differential suites assert on them to prove which engine answered.
PROFILE = os.environ.get("CS_TPU_PROFILE") == "1"
TRACE = os.environ.get("CS_TPU_TRACE") == "1"

# Random-linear-combination batch-verification switch:
# ``CS_TPU_BLS_RLC=0`` makes ``utils/bls.DeferredBatch.flush`` run the
# per-lane path (one pairing check per queued item) instead of folding
# the whole batch into 2 MSMs + ONE product pairing check.  Like
# ``CS_TPU_PROTO_ARRAY``, this snapshot is the import-time default and
# the switch re-reads the environment at call time when the variable is
# present (``utils/bls.rlc_enabled``), so a test/CI leg can flip it
# after import.
BLS_RLC = os.environ.get("CS_TPU_BLS_RLC") != "0"

# Copy-on-write columnar state store kill switch:
# ``CS_TPU_STATE_ARRAYS=0`` detaches the per-state ``StateArrays``
# column store (``state/arrays.py``): every engine access re-extracts
# its columns and commits immediately instead of sharing one extraction
# per state lineage with deferred per-epoch commits.  Like
# ``CS_TPU_PROTO_ARRAY``, this snapshot is the import-time default and
# ``state.arrays.enabled()`` re-reads the environment at call time when
# the variable is present, so a test/CI leg can flip it after import.
STATE_ARRAYS = os.environ.get("CS_TPU_STATE_ARRAYS") != "0"

# Proto-array fork-choice kill switch: ``CS_TPU_PROTO_ARRAY=0`` runs the
# spec-loop ``get_head`` / ``get_weight`` / ``get_filtered_block_tree``
# (``forks/fork_choice.py``) instead of the incremental columnar engine
# in ``forkchoice/proto_array.py``, and stores are created without an
# engine attached.  This snapshot is the default
# ``forkchoice.proto_array.enabled()`` answers with; setting the
# variable after import also works (like ``CS_TPU_VECTORIZED_EPOCH``,
# the switch re-reads the environment at call time when it is present).
PROTO_ARRAY = os.environ.get("CS_TPU_PROTO_ARRAY") != "0"

# Data-availability-sampling engine kill switch: ``CS_TPU_DAS=0`` runs
# the spec-loop eip7594 sampling bodies (one pairing per cell,
# per-blob erasure recovery — the markdown algorithms) instead of the
# batched DAS engine (``consensus_specs_tpu/das``: whole-batch
# cell-proof folding into one pairing, columnar multi-blob recovery).
# Live via :func:`switch` like the other engine flags.
# ``CS_TPU_DAS_FFT=limb`` additionally routes the engine's scalar-field
# FFTs through the limb kernels (``ops/jax_bls/fr_fft.py``: JAX device
# kernel, numpy mirror under CS_TPU_NUMPY_KERNELS=1); unset = host
# python-int FFT.
DAS = os.environ.get("CS_TPU_DAS") != "0"

# Mesh-sharded SPMD state engine kill switch: ``CS_TPU_MESH=0`` keeps
# the ``StateArrays`` validator-axis columns on one device — epoch
# sub-transitions and leaf merkleization run the single-device engines
# (``ops/epoch_kernels``, ``utils/ssz/merkle``) instead of the
# ``shard_map`` SPMD programs in ``consensus_specs_tpu/parallel/``.
# Live via :func:`switch`; the engine additionally declines on hosts
# with fewer than two addressable devices, so the switch only matters
# on a mesh (or under ``--xla_force_host_platform_device_count``).
# Engagement floors — registry/leaf sizes below which sharding is pure
# overhead — are the ``CS_TPU_MESH_MIN`` / ``CS_TPU_MESH_MERKLE_MIN``
# knobs read through :func:`knob` (``parallel/mesh_state.py``).
MESH = os.environ.get("CS_TPU_MESH") != "0"

# Durable-replay kill switch: ``CS_TPU_CHECKPOINT=0`` turns the
# recovery subsystem (``consensus_specs_tpu/recovery``) off — durable
# replays neither journal nor checkpoint, and a resume degrades to
# deterministic re-execution from genesis (byte-identical, slower).
# Live via :func:`switch`.  Cadence/retention knobs
# (``CS_TPU_CHECKPOINT_EVERY``, ``CS_TPU_CHECKPOINT_KEEP``) are read
# through :func:`knob` by the sim recovery legs; docs/recovery.md.
CHECKPOINT = os.environ.get("CS_TPU_CHECKPOINT") != "0"

# Block-serving pipeline kill switch: ``CS_TPU_SERVING=0`` makes the
# serving layer (``consensus_specs_tpu/serving``) deliver every block
# through the synchronous per-block ``on_block`` path — no window
# batching, no overlapped RLC flushes, no chunk-level state clones.
# Live via :func:`switch` like the other engine flags (the off-leg CI
# job flips it after import; a latched module constant would miss
# that — the historical import-latched-flag class this registration
# exists to prevent).  The window-depth knob (``CS_TPU_SERVING_WINDOW``)
# is read through :func:`knob` by ``serving/pipeline.py``.
SERVING = os.environ.get("CS_TPU_SERVING") != "0"

# Runtime effect sanitizer: ``CS_TPU_SANITIZER=1`` arms the dynamic
# twin of the speclint E12xx effect contracts
# (``consensus_specs_tpu/sanitizer.py``): the state store and the
# recovery writers feed a shadow effect log, and a violated contract
# (direct SSZ write under a pending deferred column, a checkpoint blob
# after its manifest, an unfsynced STEP marker or final-path rename)
# raises ``EffectViolation`` naming the E12xx rule.  Default OFF — a
# diagnostic arm, not an engine; read live through :func:`knob`
# (``sanitizer.enabled``).  Disabled overhead is bench-asserted <2%
# (``benchmarks/bench_sanitizer.py``).
SANITIZER = os.environ.get("CS_TPU_SANITIZER") == "1"

# Engine supervisor kill switch: ``CS_TPU_SUPERVISOR=0`` turns the
# health-tracking supervision layer (``consensus_specs_tpu/supervisor``)
# into a pass-through — no circuit breakers, no deadline guards, no
# sentinel audits; every dispatch behaves exactly as before PR 9.
# Live via :func:`switch` like the other engine flags.  The supervisor's
# numeric knobs (breaker threshold/window/backoff, audit sampling rate,
# deadline budget) are documented in ``docs/robustness.md`` and read by
# ``supervisor.reset()``.
SUPERVISOR = os.environ.get("CS_TPU_SUPERVISOR") != "0"
