"""trace_reduce on a small trace recorded on one TPU v5e (PR 22): two
jitted programs, f and g, run in each of three ``bench.flush``
annotations, with 20 ms sleeps between them."""
import os

import pytest

import trace_reduce as T

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "chip_probe.xplane.pb")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_file(TRACE)


def test_merge_clip_total():
    assert T.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert T.clip([(1, 4), (5, 8)], 3, 6) == [(3, 4), (5, 6)]
    assert T.total([(1, 4), (5, 8)]) == 6


def test_program_name_drops_fingerprint():
    assert T.program_name("jit_f(16969304421831028761)") == "jit_f"
    assert T.program_name("jit__j_tree_sum") == "jit__j_tree_sum"


def test_only_accelerator_planes_are_devices():
    assert T.is_device_plane("/device:TPU:0")
    assert not T.is_device_plane("/device:CUSTOM:Megascale Trace")
    assert not T.is_device_plane("/host:CPU")


def test_clock_offset_puts_device_inside_its_dispatch(profile):
    # the device clock of this trace runs ~1.8 ms behind the host's
    off = T.clock_offset(profile)
    assert 1.0e6 < off < 2.5e6


def test_busy_union_and_programs(profile):
    r = T.reduce(profile)
    assert r is not None
    # three runs each of f (~112.6 us) and g (~150.9 us)
    progs = r["per_program_s"]
    assert set(progs) == {"jit_f", "jit_g"}
    assert progs["jit_f"] == pytest.approx(3 * 112.6e-6, rel=0.01)
    assert progs["jit_g"] == pytest.approx(3 * 150.9e-6, rel=0.01)
    # busy is the union of op intervals, inside the modules' time
    assert 0.7e-3 < r["busy_s"] <= progs["jit_f"] + progs["jit_g"]
    assert r["window_s"] == pytest.approx(0.0458, rel=0.01)
    assert r["top_programs"][0][0] == "jit_g"


def test_every_flush_holds_its_device_work(profile):
    r = T.reduce(profile)
    flushes = [a for a in r["per_annotation"] if a[0] == "bench.flush"]
    assert len(flushes) == 3
    for _, wall, busy in flushes:
        assert 0 < busy < wall
    assert sum(b for _, _, b in flushes) == pytest.approx(r["busy_s"], rel=1e-6)


def test_idle_gaps_are_the_complement_named_by_host(profile):
    r = T.reduce(profile)
    gaps = r["top_gaps"]
    assert len(gaps) <= 10
    # the two 20 ms sleeps between flushes are the longest gaps
    assert [round(s, 2) for _, s in gaps[:2]] == [0.02, 0.02]
    assert all(name != "(no host event)" for name, _ in gaps[:3])
    assert r["busy_s"] + sum(s for _, s in gaps) <= r["window_s"] + 1e-9


def test_no_annotation_means_nothing_to_read(profile):
    assert T.reduce(profile, prefix="no-such-prefix.") is None
