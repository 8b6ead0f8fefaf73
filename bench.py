"""Benchmark: batched FastAggregateVerify throughput (BASELINE config #1).

Measures aggregate-signature verifications/second against the
pure-python oracle (the reference's py_ecc role, ``BASELINE.md``:
">=50x py_ecc" north star; backend ladder being replaced: reference
``eth2spec/utils/bls.py:35-53``).

Prints exactly ONE JSON line on stdout inside a wall-clock budget
(``CS_TPU_BENCH_BUDGET`` seconds, default 470), naming the platform it
measured.  It exits nonzero when nothing was measured.

* The PARENT process is pure stdlib - it never imports jax or the
  framework, so a wedged compile cannot starve its watchdog, and it
  never holds the chip.  Every measurement runs in a killable child,
  one child at a time.
* The oracle baseline clears the verification memo between reps
  (``bls.clear_verify_memo``) so it times pairings, not dict hits.
* The platform is whatever JAX says.  By default the child measures the
  batched JAX pipeline on the accelerator and fails when JAX finds none.
  The CPU is measured only when the caller asks for it with
  ``JAX_PLATFORMS=cpu``: the native C backend, then the XLA:CPU
  pipeline if the native library is unavailable; the line then says
  ``"platform": "cpu"``.
"""
import json
import os
import signal
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

BUDGET = float(os.environ.get("CS_TPU_BENCH_BUDGET", "470"))
_T0 = time.time()


def _remaining() -> float:
    return BUDGET - (time.time() - _T0)


_RESULT = {
    "metric": "FastAggregateVerify (64 pubkeys, batch)",
    "value": 0.0,
    "unit": "aggverify/s",
    "vs_baseline": 0.0,
    "partial": True,
    "stage": "init",
    "platform": "unknown",
}
_PRINTED = False


def _emit_and_exit(code=0):
    global _PRINTED
    if not _PRINTED:
        _PRINTED = True
        out = dict(_RESULT)
        out["elapsed_s"] = round(time.time() - _T0, 1)
        print(json.dumps(out), flush=True)
    os._exit(code)


def _run_child(role: str, env_overrides: dict, timeout: float):
    """Run this file in ``role`` mode; return (last-json-line, err)."""
    env = dict(os.environ, CS_TPU_BENCH_ROLE=role, **env_overrides)
    env["CS_TPU_BENCH_INNER_DEADLINE"] = str(time.time() + timeout)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, timeout=timeout, capture_output=True, cwd=_HERE)
    except subprocess.TimeoutExpired:
        return None, "timeout"
    except Exception as e:
        return None, f"{type(e).__name__}: {e}"[:200]
    for line in reversed(proc.stdout.decode().splitlines()):
        try:
            return json.loads(line), None
        except json.JSONDecodeError:
            continue
    if proc.returncode != 0:
        return None, proc.stderr.decode()[-300:]
    return None, "no-json"


# ---------------------------------------------------------------------------
# Child roles (import jax / the framework; killable by the parent)
# ---------------------------------------------------------------------------

def _role_oracle():
    """Measure the pure-python oracle: seconds per FastAggregateVerify."""
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.tools import bench_fixtures
    bls.use_py()
    pks, msg, agg = bench_fixtures.load()
    deadline = float(os.environ.get("CS_TPU_BENCH_INNER_DEADLINE", "inf"))
    times = []
    for _ in range(3):
        bls.clear_verify_memo()        # time pairings, not dict hits
        t0 = time.time()
        assert bls.FastAggregateVerify(pks, msg, agg)
        times.append(time.time() - t0)
        if time.time() + times[-1] > deadline - 2:
            break
    print(json.dumps({"py_oracle_s_per_verify":
                      sorted(times)[len(times) // 2]}), flush=True)


def _role_native():
    """Measure the native C backend (the CPU production path behind
    use_fastest; reference's milagro role) — no XLA, no compile cost."""
    from consensus_specs_tpu.ops import native_bls
    from consensus_specs_tpu.tools import bench_fixtures
    if not native_bls.available():
        print(json.dumps({"bail": "native-unavailable"}), flush=True)
        sys.exit(3)
    pks, msg, agg = bench_fixtures.load()
    deadline = float(os.environ.get("CS_TPU_BENCH_INNER_DEADLINE", "inf"))
    assert native_bls.FastAggregateVerify(pks, msg, agg)
    reps, t_acc = 0, 0.0
    while reps < 8 and (reps == 0 or
                        time.time() + t_acc / reps < deadline - 2):
        t0 = time.time()
        native_bls.FastAggregateVerify(pks, msg, agg)
        t_acc += time.time() - t0
        reps += 1
    result = {
        "platform": "cpu",
        "backend": "native",
        "batch": 1,
        "warm_s": 0.0,
        "reps": reps,
        "per_sec": 1.0 / (t_acc / reps),
    }
    print(json.dumps(result), flush=True)


def _role_device():
    """Measure the batched staged pipeline on this process's platform;
    off the CPU platform, an accelerator is required."""
    from consensus_specs_tpu.utils.jax_env import setup_compile_cache
    setup_compile_cache()
    import jax
    platform = jax.default_backend()
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(json.dumps({"bail": "no accelerator: JAX found only the "
                          "cpu platform (set JAX_PLATFORMS=cpu to measure "
                          "the host)"}), flush=True)
        sys.exit(3)
    from consensus_specs_tpu.tools import bench_fixtures
    from consensus_specs_tpu.ops import bls_jax

    pks, msg, agg = bench_fixtures.load()
    batch = bls_jax.bucket_b()
    items = [(pks, msg, agg)] * batch
    t0 = time.time()
    out = bls_jax.verify_aggregates_batch(items)   # compile + dispatch
    warm_s = time.time() - t0
    assert all(out), "bench verification must pass"
    reps, t_acc = 0, 0.0
    deadline = float(os.environ.get("CS_TPU_BENCH_INNER_DEADLINE", "inf"))
    while reps < 5 and (reps == 0 or
                        time.time() + t_acc / reps < deadline - 2):
        t0 = time.time()
        bls_jax.verify_aggregates_batch(items)
        t_acc += time.time() - t0
        reps += 1
    result = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "backend": "jax",
        "batch": batch,
        "warm_s": round(warm_s, 1),
        "reps": reps,
        "per_sec": batch / (t_acc / reps),
    }
    print(json.dumps(result), flush=True)


# ---------------------------------------------------------------------------
# Parent
# ---------------------------------------------------------------------------

def _fill_from(per_sec, batch, platform, py_per_verify, extra=None):
    _RESULT["metric"] = f"FastAggregateVerify (64 pubkeys, batch {batch})"
    _RESULT["value"] = round(per_sec, 3)
    _RESULT["vs_baseline"] = (round(per_sec * py_per_verify, 2)
                              if py_per_verify else 0.0)
    _RESULT["platform"] = platform
    _RESULT.update(extra or {})


def main():
    # absolute backstop: even if subprocess plumbing wedges, the line lands
    signal.signal(signal.SIGALRM,
                  lambda s, f: (_RESULT.__setitem__(
                      "stage", _RESULT["stage"] + " (alarm)"),
                      _emit_and_exit(1)))
    signal.alarm(max(5, int(BUDGET - 3)))

    # --- python-oracle baseline ------------------------------------
    _RESULT["stage"] = "oracle"
    data, err = _run_child("oracle", {}, min(100.0, BUDGET * 0.25))
    py_per_verify = (data or {}).get("py_oracle_s_per_verify", 0.0)
    if py_per_verify:
        _RESULT["py_oracle_s_per_verify"] = round(py_per_verify, 3)
    else:
        _RESULT["oracle_error"] = (err or "")[:200]

    # --- measurement: the accelerator, or the host when asked for ---
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # native C first (the production CPU path: no compile); the
        # XLA:CPU pipeline only when the native library is unavailable,
        # at the staged pipeline's lane bucket (pairing.LANE_BUCKET)
        attempts = [("native", {}),
                    ("device", {"CS_TPU_BLS_BATCH":
                                os.environ.get("CS_TPU_BLS_BATCH", "8")})]
    else:
        attempts = [("device", {})]
    for role, overrides in attempts:
        _RESULT["stage"] = f"measuring-{role}"
        data, err = _run_child(role, overrides, max(30.0, _remaining() - 8))
        if data is None or "bail" in data:
            _RESULT[f"attempt_{role}"] = \
                (err or (data or {}).get("bail", ""))[:200]
            continue
        extra = {k: data[k] for k in ("backend", "device_kind",
                                      "device_count") if k in data}
        _fill_from(data["per_sec"], data["batch"], data["platform"],
                   py_per_verify,
                   dict(extra, jax_warm_s=data["warm_s"], reps=data["reps"],
                        partial=False,
                        stage=f"measured-{data['platform']}"))
        _emit_and_exit(0)
    _emit_and_exit(1)


if __name__ == "__main__":
    role = os.environ.get("CS_TPU_BENCH_ROLE")
    try:
        if role == "oracle":
            _role_oracle()
        elif role == "native":
            _role_native()
        elif role == "device":
            _role_device()
        else:
            main()
    except Exception as e:
        if role:
            raise
        _RESULT["error"] = f"{type(e).__name__}: {e}"[:300]
        _emit_and_exit(1)
