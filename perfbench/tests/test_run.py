"""The command refuses the host CPU: no result, a nonzero exit."""
import json
import os
import subprocess
import sys

import tiny


def test_refuses_without_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.PERFBENCH, "run.py"),
         "--workload", "blocksig-1m.full", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_benchmark_names_resolve_to_files():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(tiny.ROOT, c["file"]))
    for w in bench["workloads"]:
        with open(os.path.join(tiny.PERFBENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.exists(os.path.join(tiny.PERFBENCH, "drivers",
                                           driver + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(tiny.PERFBENCH, "metrics",
                                           m["name"] + ".py"))
