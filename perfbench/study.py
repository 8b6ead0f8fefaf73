"""Readings that set the limits of a cell's output check, on the chip.

    python3 perfbench/study.py --workload <cell> --seeds 11,12,... \
        --control-seeds 21,22,23 --seconds 8 --out chiprun_out/study.json

In one process, so that set-up is paid once: for every seed, the cell's
own generator, warm-up, a short window at the cell's load and the
output check, as a run makes them (the lower readings); then, for every
control seed, the control put in the program's place and judged by the
same check (the upper readings).  The control is the plain reference
with the batch-soundness guarantee the configuration states broken:
the RLC scalars drawn at 64 bits instead of 128.  Not part of a
benchmark run.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run
    sys.path.insert(1, run.ROOT)
    bench, cell, cfg, mix = run.load_cell(args.workload)
    clog, devices = run.setup_jax(cell["chips"])
    driver = run.load_driver(mix)
    report = {"cell": cell["name"], "device": devices[0].device_kind,
              "program": [], "control": []}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        work = driver.build(cfg, mix, seed)
        driver.warm(work)
        _, ops = driver.window(work, args.seconds, run.Tracer(False))
        compared = driver.check(work, ops)
        report["program"].append({"seed": seed, "ops": len(ops),
                                  "compared": compared,
                                  "wall_s": time.perf_counter() - t0})
        print("program", seed, len(ops), compared, flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",")]:
        work = driver.build(cfg, mix, seed)
        compared = driver.control_check(work)
        report["control"].append({"seed": seed, "compared": compared})
        print("control", seed, compared, flush=True)
    report["compile"] = clog.since((0, 0))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
