"""Tiny sizes for CPU rehearsals of the drivers (the numpy mirror of the
BLS limb kernels runs the same staged pipeline eagerly)."""
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)

TINY_SIGFLUSH = {
    "distinct_keys": 16, "committee_width": 4, "max_attestations": 3,
    "sync_committee_size": 4, "singles": 2,
}


def setup_paths():
    for p in (PERFBENCH, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def config(name, **override):
    with open(os.path.join(PERFBENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(override)
    return cfg


def mix(name, **override):
    with open(os.path.join(PERFBENCH, "traffic", name + ".json")) as f:
        m = json.load(f)
    m.update(override)
    return m


def driver(name):
    setup_paths()
    import importlib
    return importlib.import_module("drivers." + name)


@contextlib.contextmanager
def patched(obj, attr, value):
    orig = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, orig)


class NoTrace:
    """The window's tracer when nothing is traced."""

    def around(self, i, name):
        return contextlib.nullcontext()
