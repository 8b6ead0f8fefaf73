"""The pubkey table behind the device BLS paths (``ops/bls_jax._PubkeyCache``).

Every packed key's affine Montgomery rows live in one contiguous table,
and a flush gathers its (lanes x width) pubkey grid from it by slot.
These tests hold the gathered grid to ``PT.g1_pack``'s encoding and to
the row-by-row stack it replaced, on the jax CPU backend and on the
numpy kernels; slot reuse after an LRU eviction; growth in the middle
of a flush's keys; the ``bls.pk_table.rows_added`` counter; and the
table's bookkeeping under concurrent threads.
"""
import json
import os
import random
import subprocess
import sys
import threading

import numpy as np
import pytest

from consensus_specs_tpu.obs import registry
from consensus_specs_tpu.ops import bls_jax, bls_rlc
from consensus_specs_tpu.ops.bls12_381.curve import (
    G1_GENERATOR, G1Point, G2Point, G2_GENERATOR)
from consensus_specs_tpu.ops.jax_bls import limbs as L
from consensus_specs_tpu.ops.jax_bls import pairing as PR
from consensus_specs_tpu.ops.jax_bls import points as PT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIDTHS = (0, 1, 3, 512)
_ROWS_ADDED = registry.counter("bls.pk_table.rows_added")


def _keys(n):
    """[k]G for k = 1..n (= SkToPk(k)), as compressed bytes48."""
    out, p = [], G1_GENERATOR
    for _ in range(n):
        out.append(p.to_compressed())
        p = p + G1_GENERATOR
    return out


class _Stop(Exception):
    """Raised in place of the pubkey tree sum, the flush's first device
    program: what it was handed is the grid under test."""


def _tree_sum_input(run):
    """The pytree ``run()`` hands ``bls_jax._j_tree_sum``."""
    seen = []

    def tree_sum(pk_pts):
        seen.append(pk_pts)
        raise _Stop
    orig = bls_jax._j_tree_sum
    bls_jax._j_tree_sum = tree_sum
    try:
        run()
    except _Stop:
        pass
    finally:
        bls_jax._j_tree_sum = orig
    assert len(seen) == 1
    return tuple(np.asarray(a) for a in seen[0])


def _row_by_row(point_rows, lanes, width):
    """The stack the table replaced: one (x, y) limb pair per key,
    identity rows appended to each lane."""
    xs, ys, zs = [], [], []
    for row in point_rows + [[]] * (lanes - len(point_rows)):
        pad = width - len(row)
        xs += [L.fq_const(p.x.n) for p in row] + [L.ZERO] * pad
        ys += [L.fq_const(p.y.n) for p in row] + [L.ONE_M] * pad
        zs += [L.ONE_M] * len(row) + [L.ZERO] * pad
    return tuple(np.stack(c).reshape(lanes, width, L.NLIMB)
                 for c in (xs, ys, zs))


def _g1_packed(point_rows, lanes, width):
    """``PT.g1_pack`` of the same points, identity-padded."""
    flat = []
    for row in point_rows + [[]] * (lanes - len(point_rows)):
        flat += row + [G1Point.inf()] * (width - len(row))
    return tuple(np.asarray(a).reshape(lanes, width, L.NLIMB)
                 for a in PT.g1_pack(flat))


def _same(a, b):
    return all(x.dtype == y.dtype and x.shape == y.shape
               and np.array_equal(x, y) for x, y in zip(a, b))


def grid_matches(width: int) -> bool:
    """An RLC flush of a ``width``-key lane and a 2-key lane: the grid
    handed to the tree sum equals ``PT.g1_pack`` of the same points and
    the row-by-row stack, bit for bit."""
    keys = _keys(width + 2)
    lanes = [keys[:width], keys[width:]]
    slots = bls_jax._slot_rows(lanes)
    grid = _tree_sum_input(lambda: bls_jax.rlc_combined_check(
        slots, [b"", b""], [G2Point.inf()] * 2, [1, 1]))
    points = [[bls_jax._decompress_g1(k) for k in lane] for lane in lanes]
    n_lanes = PR.lane_bucket(2)
    n_pad = max(bls_jax._N_MIN, bls_jax._pow2(max(width, 2)))
    return (_same(grid, _g1_packed(points, n_lanes, n_pad))
            and _same(grid, _row_by_row(points, n_lanes, n_pad)))


@pytest.fixture
def fresh_table(monkeypatch):
    """A fresh pubkey table in place of the process's; ``make(maxsize,
    capacity)`` replaces it again."""
    def make(maxsize=1 << 12, capacity=1 << 10):
        cache = bls_jax._PubkeyCache(maxsize, capacity)
        monkeypatch.setattr(bls_jax, "_g1_cache", cache)
        return cache
    return make


@pytest.fixture(scope="module")
def numpy_kernel_grids():
    """``grid_matches`` for every width in one numpy-kernel process."""
    code = ("import sys, json; sys.path[:0] = [%r, %r]; "
            "import test_bls_pk_table as t; "
            "print('GRIDS', json.dumps({w: t.grid_matches(w) "
            "for w in t.WIDTHS}))" % (REPO, os.path.join(REPO, "tests")))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, timeout=600,
        cwd=REPO, env=dict(os.environ, CS_TPU_NUMPY_KERNELS="1"))
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    line = [ln for ln in proc.stdout.decode().splitlines()
            if ln.startswith("GRIDS ")][0]
    return {int(w): ok for w, ok in json.loads(line[6:]).items()}


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("kernels", ["jax", "numpy"])
def test_grid_equals_g1_pack(kernels, width, fresh_table, request):
    if kernels == "jax":
        fresh_table()
        assert grid_matches(width)
    else:
        assert request.getfixturevalue("numpy_kernel_grids")[width]


@pytest.mark.parametrize("width", [1, 3, 512])
def test_lane_path_grid_equals_g1_pack(width, fresh_table):
    """The per-lane path (the bisect, lone Verify/FastAggregateVerify)
    gathers its grid the same way."""
    fresh_table()
    keys = _keys(width)
    sig = G2_GENERATOR.to_compressed()
    grid = _tree_sum_input(lambda: bls_jax._verify_aggregates_batch(
        [(keys, b"m", sig)]))
    points = [[bls_jax._decompress_g1(k) for k in keys]]
    lanes = grid[0].shape[0]
    n_pad = max(bls_jax._N_MIN, bls_jax._pow2(width))
    assert _same(grid, _g1_packed(points, lanes, n_pad))


@pytest.mark.parametrize("capacity", [1, 4, 7])
def test_growth_mid_flush(capacity, fresh_table):
    """The table doubles (up to maxsize + 1 rows) while one flush's keys
    are packed; rows written before the growth survive it."""
    cache = fresh_table(maxsize=20, capacity=capacity)
    keys = _keys(19)
    early = bls_jax._slot_rows([keys[:2]])[0]
    lanes = [keys[:10], keys[10:]]
    slots = bls_jax._slot_rows(lanes)
    assert slots[0][:2] == early
    assert len(cache.x) == len(cache.y) <= 21
    assert len(cache.x) >= 20
    grid = cache.grid(slots, 2, 16)
    points = [[bls_jax._decompress_g1(k) for k in lane] for lane in lanes]
    assert _same(grid, _g1_packed(points, 2, 16))


def test_rows_added_zero_on_second_flush(fresh_table, monkeypatch):
    """A second flush of the same keys writes no row: the counter the
    benchmark window should read 0."""
    fresh_table()
    keys = _keys(6)
    sig = G2_GENERATOR.to_compressed()
    items = [(keys[:4], b"a", sig), (keys[3:], b"b", sig)]

    def no_device(*_args, **_kw):
        raise _Stop
    monkeypatch.setattr(bls_jax, "_j_tree_sum", no_device)
    counts = []
    for _ in range(2):
        before = _ROWS_ADDED.total()
        with pytest.raises(_Stop):
            bls_rlc._check_jax(items, (), [1, 2])
        counts.append(_ROWS_ADDED.total() - before)
    assert counts == [6, 0]


def test_invalid_key_gets_no_slot(fresh_table):
    cache = fresh_table()
    bad = bytes([0xC0]) + b"\x00" * 47          # the identity: invalid
    good = _keys(1)[0]
    assert bls_jax._slot_rows([[good, bad], [good]]) == [None, [1]]
    assert cache[bad] == [None, None]


def test_batch_larger_than_cache_raises(fresh_table):
    """A batch whose own keys would evict each other is refused."""
    fresh_table(maxsize=4)
    with pytest.raises(ValueError):
        bls_jax._slot_rows([_keys(3), _keys(2)])


def test_eviction_returns_slot(fresh_table):
    cache = fresh_table(maxsize=4, capacity=2)
    keys = _keys(6)
    first = bls_jax._slot_rows([keys[:4]])[0]
    bls_jax._slot_rows([[keys[1], keys[2], keys[3]]])    # key 0 is LRU
    (taken,) = bls_jax._slot_rows([[keys[4]]])[0]
    assert keys[0] not in cache and taken == first[0]
    (again,) = bls_jax._slot_rows([[keys[0]]])[0]        # evicts key 1
    assert again == first[1] and keys[1] not in cache
    assert len(cache.x) == 5 and cache._next == 5


_NUMPY_EVICTION_CHECK = r"""
import sys
sys.path.insert(0, %(repo)r)
from consensus_specs_tpu.ops.jax_bls.backend import NUMPY_KERNELS
assert NUMPY_KERNELS
from consensus_specs_tpu.ops import bls_jax, bls_rlc
from consensus_specs_tpu.utils import bls

bls.use_py()
pks = [bls.SkToPk(i) for i in range(1, 6)]
msgs = [bytes([i]) * 32 for i in range(1, 6)]
sigs = [bls.Sign(i, m) for i, m in zip(range(1, 6), msgs)]
bls_jax._g1_cache = cache = bls_jax._PubkeyCache(4, 2)
first = bls_jax._slot_rows([pks[:4]])[0]
(taken,) = bls_jax._slot_rows([[pks[4]]])[0]   # evicts key 1, takes its slot
(again,) = bls_jax._slot_rows([[pks[0]]])[0]   # key 1 back, in key 2's slot
assert taken == first[0] and again == first[1], (first, taken, again)
items = [([pks[4]], msgs[4], sigs[4]), ([pks[0]], msgs[0], sigs[0])]
print("REUSED", bls_rlc._check_jax(items, (), [3, 5]))
stale = bls_jax.rlc_combined_check(
    [[first[0]]], [msgs[0]], [bls_jax._decompress_g2(sigs[0])], [3])
print("STALE", stale)
"""


def test_slot_reuse_after_eviction_verifies():
    """With a 4-key cache: an evicted and re-added key and the key that
    took its slot both verify in one RLC flush, and the evicted key's
    old slot (now another key's row) fails (numpy kernels)."""
    proc = subprocess.run(
        [sys.executable, "-c", _NUMPY_EVICTION_CHECK % {"repo": REPO}],
        env=dict(os.environ, CS_TPU_NUMPY_KERNELS="1"),
        capture_output=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    out = proc.stdout.decode()
    assert "REUSED True" in out and "STALE False" in out, out[-500:]


def test_concurrent_resolution_keeps_table_consistent(fresh_table):
    """Threads resolving overlapping batches through a cache smaller than
    their keys: afterwards every resident key owns a distinct slot whose
    row is its point, and every allocated slot is resident or free."""
    cache = fresh_table(maxsize=24, capacity=2)
    keys = _keys(64)
    errors = []

    def worker(seed):
        rng = random.Random(seed)
        try:
            for _ in range(60):
                bls_jax._slot_rows([rng.sample(keys, 4)])
        except Exception as exc:      # surfaced below
            errors.append(exc)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    live = {key: e[1] for key, e in cache.items() if e[1] is not None}
    assert len(set(live.values())) == len(live)
    assert sorted(list(live.values()) + cache._free) == \
        list(range(1, cache._next))
    for key, slot in live.items():
        pt = cache[key][0]
        assert np.array_equal(cache.x[slot], L.fq_const(pt.x.n))
        assert np.array_equal(cache.y[slot], L.fq_const(pt.y.n))
    assert np.array_equal(cache.x[0], L.ZERO)
    assert np.array_equal(cache.y[0], L.ONE_M)
