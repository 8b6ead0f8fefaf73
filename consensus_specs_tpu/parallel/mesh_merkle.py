"""Leaf-span merkleization over the device mesh.

A full tree build (the chunk-packed column commit's ``set_leaves``, a
cold ``hash_tree_root`` of a 1M-entry balances/validators list — both
under the PR-3 ``hash_forest()`` flush) hashes every level through one
host dispatch per level.  This module partitions the LEAF layer into
``S`` equal spans (``S`` = the largest power-of-two device count), zero
-pads to the span grid, and runs one ``shard_map`` SPMD program in
which each device hashes its own span subtree — ``log2(width/S)``
levels of batched 64-byte SHA-256 compressions, shard-local, ZERO
collectives — through the same scan-based compression kernel as the
batched pair hasher (``ops/sha256``).  The host then combines only the
top ``log2(S)`` levels over the ``S`` span roots.

Byte-identity argument: zero-chunk padding IS the SSZ virtual padding —
``zero_hashes[i+1] = H(zero_hashes[i] * 2)``, so a padded span computes
exactly the zero-subtree values the sequential build reads from the
precomputed table; the materialized levels are truncated back to the
occupied prefix (``ceil(count / 2**i)`` nodes at level ``i``), so the
resulting ``IncrementalTree.levels`` list is byte-identical to the
sequential build — every later incremental update sees the same tree.
``tests/test_mesh.py`` fuzzes this across ragged sizes.

Site contract (``mesh.merkle``): supervisor admission, ``faults.check``
dispatch hook, counted reason-labeled fallbacks onto the sequential
per-level build, sentinel audits against a full sequential recompute
(authoritative — a corrupted device level cannot enter a tree past its
audit), and the ``CS_TPU_MESH=0`` CI off-leg.
"""
import numpy as np

from consensus_specs_tpu import faults, supervisor
from consensus_specs_tpu.obs import registry as obs_registry
from consensus_specs_tpu.obs.tracing import span
from consensus_specs_tpu.parallel import mesh_state

SITE = "mesh.merkle"

_C_MESH = obs_registry.counter("mesh.merkle").labels(path="mesh")
_C_SPAN_LEVELS = obs_registry.counter("mesh.merkle.span_levels").labels()
# injected/deadline only — shape routing (too small, non-pow2 devices)
# is a policy decline counted nowhere, the merkle.fallbacks convention
_FALLBACKS = {
    "injected": obs_registry.counter(
        "mesh.merkle.fallbacks").labels(reason="injected"),
    "deadline": obs_registry.counter(
        "mesh.merkle.fallbacks").labels(reason="deadline"),
    "device_loss": obs_registry.counter(
        "mesh.merkle.fallbacks").labels(reason="device_loss"),
}

_PROGRAMS = {}


def _span_shards() -> int:
    """Largest power-of-two device count: spans must be power-of-two
    subtrees for the combine levels to align with the tree structure."""
    n = mesh_state.device_count()
    return 1 << (n.bit_length() - 1)


def _program(mesh, local_depth):
    key = (mesh, local_depth)
    prog = _PROGRAMS.get(key)
    if prog is None:
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from consensus_specs_tpu.ops.sha256 import _H0, _PAD64, _compress

        def sha_rows(words):
            m = words.shape[0]
            st = jnp.broadcast_to(jnp.asarray(_H0), (m, 8))
            st = _compress(st, words)
            return _compress(st,
                             jnp.broadcast_to(jnp.asarray(_PAD64), (m, 16)))

        def local(words):
            outs = []
            cur = words
            for _ in range(local_depth):  # noqa: J203 (static: span depth)
                m = cur.shape[0]
                cur = sha_rows(cur.reshape(m // 2, 16))
                outs.append(cur)
            return tuple(outs)

        axis = mesh_state.AXIS
        # check_vma off: the SHA-256 scan's constant initial state is
        # not device-varying while the hashed words are, and every
        # output is sharded (no replication claim to check)
        prog = jax.jit(shard_map(
            local, mesh=mesh, in_specs=P(axis),
            out_specs=tuple(P(axis) for _ in range(local_depth)),
            check_vma=False))
        _PROGRAMS[key] = prog
    return prog


def _sequential_levels(data, depth):
    """The single-device build, verbatim (``IncrementalTree._build``'s
    loop) — the audit oracle and the counted-fallback target."""
    from consensus_specs_tpu.utils.ssz import merkle
    levels = [bytearray(data)]
    for level in range(depth):
        levels.append(bytearray(merkle.hash_layer(
            merkle._padded_layer(levels[-1], level))))
    return levels


def build_levels(data, depth: int):
    """All ``depth + 1`` tree levels of a whole-chunk leaf buffer, or
    None when the mesh path declines (engine off, below the
    ``CS_TPU_MESH_MERKLE_MIN`` floor, or a counted fallback) — the
    caller then builds sequentially.  Levels are byte-identical to the
    sequential build (module docstring)."""
    from consensus_specs_tpu.utils.ssz import merkle
    count = len(data) // 32
    if count == 0 or not mesh_state.merkle_engaged(count):
        return None
    n_dev = _span_shards()
    if n_dev < 2:
        return None
    full_width = merkle.next_power_of_two(count)
    if full_width < 2 * n_dev or depth < merkle.ceil_log2(full_width):
        return None
    if not supervisor.admit(SITE):
        return None
    checked = False
    while True:
        # span grid re-derives per attempt: a device loss mid-dispatch
        # shrinks the surviving set and the retry re-shards elastically
        local_depth = merkle.ceil_log2(full_width // n_dev)
        devices = None
        if n_dev != mesh_state.device_count():
            devices = mesh_state.active_devices()[:n_dev]
        mesh = mesh_state.build_mesh(devices=devices)
        try:
            if not checked:
                faults.check(SITE)
                checked = True
            with supervisor.deadline_scope(SITE):
                with span("mesh.merkle.dispatch"):
                    if faults.loss_armed(SITE):
                        raise mesh_state.DeviceLoss(SITE)
                    padded = bytes(data) \
                        + b"\x00" * ((full_width - count) * 32)
                    words = np.frombuffer(padded, dtype=">u4") \
                        .astype(np.uint32).reshape(full_width, 8)
                    with mesh_state.x64():
                        mesh_state._C_PLACE["leaves"].add()
                        outs = _program(mesh, local_depth)(words)
                    raw = [np.asarray(o).astype(">u4").tobytes()
                           for o in outs]
        except mesh_state.DeviceLoss:
            mesh_state.lose_device(SITE)
            faults.count_fallback(_FALLBACKS, None,
                                  organic="device_loss", site=SITE)
            n_dev = _span_shards()
            if n_dev >= 2 and full_width >= 2 * n_dev \
                    and mesh_state.enabled() \
                    and mesh_state.merkle_engaged(count):
                continue
            return None     # survivors below the grid: sequential build
        except (faults.InjectedFault, supervisor.DeadlineExceeded) as exc:
            faults.count_fallback(_FALLBACKS, exc, organic="injected",
                                  site=SITE)
            return None
        break
    if faults.corrupt_armed(SITE):
        # silent-corruption injection (sentinel-audit test vector): one
        # flipped bit in the top span-root layer — the combined root
        # and every level above it go quietly wrong
        top = bytearray(raw[-1])
        top[0] ^= 1
        raw[-1] = bytes(top)
    # truncate each level to the occupied prefix: nodes right of it are
    # virtual (zero_hashes) in the sequential representation
    levels = [bytearray(data)]
    occ = count
    for i in range(local_depth):
        occ = (occ + 1) // 2
        levels.append(bytearray(raw[i][:occ * 32]))
    # host combine: the top log2(S) levels over the span roots, plus
    # the virtual-zero tail up to the tree limit — the sequential loop
    for level in range(local_depth, depth):
        levels.append(bytearray(merkle.hash_layer(
            merkle._padded_layer(levels[-1], level))))
    if supervisor.audit_due(SITE):
        golden = _sequential_levels(data, depth)
        ok = all(bytes(a) == bytes(b) for a, b in zip(levels, golden))
        supervisor.audit_result(
            SITE, ok, f"mesh span-built levels diverged from the "
            f"sequential build ({count} chunks, {n_dev} spans)")
        if not ok:
            return golden
    else:
        supervisor.note_success(SITE)
    _C_MESH.add()
    _C_SPAN_LEVELS.add(local_depth)
    return levels
