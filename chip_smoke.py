"""Bring-up smoke for one TPU v5e: the block-verification main path.

``python chip_smoke.py`` runs, in this one process on one chip:

* **Leg A** - a block's signatures at mainnet width: 128
  ``FastAggregateVerify`` aggregates of 512 pubkeys, one 512-key sync
  aggregate and the proposer + randao singles, flushed through
  ``bls.use_fastest()`` (which must pick ``jax``) as ONE RLC product
  pairing; then the same batch with one aggregate's signature swapped,
  which must answer False, name that index, and match the native
  backend item by item.
* **Leg B** - ``spec.state_transition`` at the deneb mainnet preset over
  an 8192-validator genesis: two consecutive signed blocks, each with
  ``MAX_ATTESTATIONS`` attestations and a full 512-member sync
  aggregate, BLS on; each post-state root must equal the native
  backend's.

After each leg every ``*.fallbacks`` counter and ``bls.flush{path=
fallback}`` must read 0 (bar the one bisect the tampered flush books),
no supervisor breaker may be open, and the arrays the flushes produced
must live on the TPU.

``python chip_smoke.py --chips 4`` runs only the four-chip path: one
altair epoch transition at the mainnet preset over a 2^20-validator
registry, with the mesh-sharded state engine over four devices and
again with the mesh off on one device; the roots must be identical,
the psum census must equal ``mesh_epoch.PSUM_BUDGET``, and placements
must span all four devices.

Earlier lines print what was found (wall times with cold compile kept
apart from warm runs, compiled-program counts, counters, device kind);
details go to ``chiprun_out/chip_smoke*.json``.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
It exits nonzero, without that line, when JAX finds no TPU or any check
fails.
"""
import argparse
import contextlib
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from consensus_specs_tpu.utils.jax_env import setup_compile_cache  # noqa: E402


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Compile accounting: JAX's own compile-duration events
# ---------------------------------------------------------------------------

class CompileLog:
    """Counts XLA backend compilations (and their seconds) per program
    name, plus the host's trace + lowering seconds, from
    ``jax.monitoring`` duration events."""

    def __init__(self):
        self.events = []            # (name, backend compile seconds)
        self.lower_s = 0.0

    def __call__(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kwargs.get("fun_name", "?"), duration))
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.lower_s += duration

    def mark(self):
        return len(self.events)

    def since(self, mark):
        ev = self.events[mark:]
        per = {}
        for name, s in ev:
            n, t = per.get(name, (0, 0.0))
            per[name] = (n + 1, t + s)
        return {"programs": len(ev),
                "compile_s": sum(s for _, s in ev),
                "per_program": {k: {"n": n, "s": t}
                                for k, (n, t) in sorted(per.items())}}


def install_compile_log():
    import jax
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    return log


# ---------------------------------------------------------------------------
# Post-leg invariants
# ---------------------------------------------------------------------------

_FALLBACK_KEY = re.compile(r"\.fallbacks(\{|$)|^bls\.flush\{.*path=fallback")


def fallback_deltas(delta) -> dict:
    return {k: v for k, v in delta.items()
            if v and _FALLBACK_KEY.search(k)}


def check_after_leg(leg, delta, expect_fallbacks, placed):
    from consensus_specs_tpu import supervisor
    fb = fallback_deltas(delta)
    say(f"{leg}: fallback counters {fb or 'all 0'}")
    check(fb == expect_fallbacks,
          f"{leg}: fallback counters {fb}, expected {expect_fallbacks}")
    open_sites = {s: st for s, st in supervisor.states().items()
                  if st != "closed"}
    check(not open_sites, f"{leg}: supervisor breakers not closed: "
          f"{open_sites}")
    platforms = sorted({p for p in placed})
    say(f"{leg}: flush outputs on {platforms} ({len(placed)} arrays)")
    check(placed and platforms == ["tpu"],
          f"{leg}: flush outputs not on the TPU: {platforms}")


@contextlib.contextmanager
def flush_placements():
    """Record the platform of every array the staged pairing finishers
    return (the last device program of each flush)."""
    from consensus_specs_tpu.ops.jax_bls import pairing as PR
    seen = []
    originals = {}

    def wrap(name):
        orig = getattr(PR, name)
        originals[name] = orig

        def recorded(*args, **kwargs):
            out = orig(*args, **kwargs)
            seen.extend(d.platform for d in out.devices())
            return out
        setattr(PR, name, recorded)

    for name in ("staged_product_pairing_check", "staged_pairing_check"):
        wrap(name)
    try:
        yield seen
    finally:
        for name, fn in originals.items():
            setattr(PR, name, fn)


# ---------------------------------------------------------------------------
# Leg A: one block's signatures, flushed as one RLC pairing
# ---------------------------------------------------------------------------

LEG_A_AGGREGATES = 128      # MAX_ATTESTATIONS
LEG_A_WIDTH = 512           # ~490-member committees at ~1M validators
SYNC_WIDTH = 512            # SYNC_COMMITTEE_SIZE


def leg_a_items():
    """128 x 512-key aggregates + a 512-key sync aggregate + proposer and
    randao singles; one Sign per aggregate (H(m)^sum(sk) equals the
    members' aggregate signature), native signing."""
    from consensus_specs_tpu.ops import native_bls
    from consensus_specs_tpu.ops.bls12_381.fields import R_ORDER
    from consensus_specs_tpu.test_infra.keys import privkeys, pubkey
    n = len(privkeys)

    def aggregate(tag, members):
        msg = tag.ljust(32, b"\0")
        return ([pubkey(sk) for sk in members], msg,
                native_bls.Sign(sum(members) % R_ORDER, msg))

    w = LEG_A_WIDTH
    items = [aggregate(b"att-%d" % a,
                       [privkeys[(a * w + j) % n] for j in range(w)])
             for a in range(LEG_A_AGGREGATES)]
    items.append(aggregate(b"sync", [privkeys[n - 1 - j]
                                     for j in range(SYNC_WIDTH)]))
    for s, tag in enumerate((b"proposer", b"randao")):
        sk = privkeys[s]
        msg = tag.ljust(32, b"\0")
        items.append(([pubkey(sk)], msg, native_bls.Sign(sk, msg)))
    return items


def flush(items):
    from consensus_specs_tpu.utils import bls
    batch = bls.DeferredBatch()
    for pks, msg, sig in items:
        batch.add(pks, msg, sig)
    t0 = time.perf_counter()
    ok = batch.flush()
    return ok, batch.last_results, time.perf_counter() - t0


def leg_a(clog, report):
    from consensus_specs_tpu.ops import native_bls
    from consensus_specs_tpu.test_infra.metrics import counting
    from consensus_specs_tpu.utils import bls

    t0 = time.perf_counter()
    items = leg_a_items()
    report["build_s"] = time.perf_counter() - t0
    say(f"leg A: built {len(items)} items "
        f"({sum(len(i[0]) for i in items)} pubkeys) in "
        f"{report['build_s']:.1f}s")
    bls.use_fastest()
    check(bls.backend_name() == "jax",
          f"use_fastest picked {bls.backend_name()!r}, not 'jax'")

    with counting() as delta, flush_placements() as placed:
        runs = []
        for label in ("cold", "warm", "warm"):
            mark = clog.mark()
            with counting() as d1:
                ok, _, dt = flush(items)
            comp = clog.since(mark)
            runs.append({"label": label, "wall_s": dt, **comp})
            say(f"leg A valid flush ({label}): {dt:.3f}s, "
                f"{comp['programs']} programs compiled "
                f"({comp['compile_s']:.1f}s compiling)")
            check(ok is True, f"valid flush ({label}) answered {ok}")
            check(d1["bls.pairings"] == 1 and d1["bls.flush{path=rlc}"] == 1,
                  f"valid flush ({label}): pairings={d1['bls.pairings']} "
                  f"rlc={d1['bls.flush{path=rlc}']}, expected 1 and 1")

        bad_index = len(items) // 3
        tampered = list(items)
        pks, msg, _ = tampered[bad_index]
        tampered[bad_index] = (pks, msg, items[bad_index + 1][2])
        mark = clog.mark()
        ok, results, dt = flush(tampered)
        comp = clog.since(mark)
        runs.append({"label": "tampered", "wall_s": dt, **comp})
        say(f"leg A tampered flush (RLC, then per-lane bisect): {dt:.3f}s, "
            f"{comp['programs']} programs compiled "
            f"({comp['compile_s']:.1f}s compiling)")
        check(ok is False, f"tampered flush answered {ok}")
        failed = [i for i, r in enumerate(results) if not r]
        check(failed == [bad_index],
              f"tampered flush blamed {failed}, expected [{bad_index}]")
        t0 = time.perf_counter()
        native = [native_bls.FastAggregateVerify(p, m, s)
                  for p, m, s in tampered]
        report["native_verdicts_s"] = time.perf_counter() - t0
        check(native == results,
              "jax per-item verdicts differ from the native backend at "
              f"{[i for i, (a, b) in enumerate(zip(native, results)) if a != b]}")
    report["runs"] = runs
    report["fallbacks"] = fallback_deltas(delta)
    check_after_leg("leg A", delta,
                    {"bls.flush{path=fallback,reason=bisect}": 1}, placed)


# ---------------------------------------------------------------------------
# Leg B: state_transition at the deneb mainnet preset
# ---------------------------------------------------------------------------

def build_block(spec, state):
    """A signed block for ``state.slot + 1`` carrying MAX_ATTESTATIONS
    attestations (every committee of the slot, duplicated up to the cap,
    as ``benchmarks/bench_all.py`` builds them) and a full sync
    aggregate; ``state`` advances to the block's post-state."""
    from consensus_specs_tpu.test_infra import block as blk
    from consensus_specs_tpu.test_infra.attestations import \
        get_valid_attestation
    from consensus_specs_tpu.test_infra.sync_committee import (
        compute_aggregate_sync_committee_signature,
        compute_committee_indices)
    committees = spec.get_committee_count_per_slot(
        state, spec.get_current_epoch(state))
    base = [get_valid_attestation(spec, state, state.slot, index=i,
                                  signed=True)
            for i in range(committees)]
    atts = []
    while len(atts) < spec.MAX_ATTESTATIONS:
        atts.extend(base[:spec.MAX_ATTESTATIONS - len(atts)])
    block = blk.build_empty_block(spec, state, state.slot + 1)
    for att in atts:
        block.body.attestations.append(att)
    members = compute_committee_indices(state)
    block.body.sync_aggregate.sync_committee_bits = [True] * len(members)
    block.body.sync_aggregate.sync_committee_signature = \
        compute_aggregate_sync_committee_signature(
            spec, state, block.slot - 1, members,
            block_root=block.parent_root)
    return blk.state_transition_and_sign_block(spec, state, block)


def leg_b(clog, report, n_blocks=2):
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.test_infra.genesis import create_genesis_state
    from consensus_specs_tpu.test_infra.keys import privkeys
    from consensus_specs_tpu.test_infra.metrics import counting
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.ssz import hash_tree_root

    t0 = time.perf_counter()
    spec = build_spec("deneb", "mainnet")
    genesis = create_genesis_state(
        spec, [spec.MAX_EFFECTIVE_BALANCE] * len(privkeys),
        spec.MAX_EFFECTIVE_BALANCE)
    bls.bls_active = True
    bls.use_native()              # signing + block building: native C
    state = genesis.copy()
    blocks = [build_block(spec, state) for _ in range(n_blocks)]
    report["build_s"] = time.perf_counter() - t0
    say(f"leg B: deneb/mainnet genesis of {len(privkeys)} validators + "
        f"{n_blocks} blocks x {len(blocks[0].message.body.attestations)} "
        f"attestations + full sync aggregate built in "
        f"{report['build_s']:.1f}s")

    def replay(label):
        bls.clear_verify_memo()   # every signature really verified
        state = genesis.copy()
        roots, times = [], []
        for i, signed in enumerate(blocks):
            mark = clog.mark()
            with counting() as d1:
                t0 = time.perf_counter()
                spec.state_transition(state, signed)
                times.append(time.perf_counter() - t0)
            comp = clog.since(mark)
            roots.append(bytes(hash_tree_root(state)))
            say(f"leg B {label} block {i + 1} (slot {signed.message.slot})"
                f": {times[-1]:.3f}s, {comp['programs']} programs compiled "
                f"({comp['compile_s']:.1f}s compiling), pairings="
                f"{d1['bls.pairings']}, rlc={d1['bls.flush{path=rlc}']}")
            check(d1["bls.pairings"] == 1 and d1["bls.flush{path=rlc}"] == 1,
                  f"leg B {label} block {i + 1}: expected one RLC pairing")
            report.setdefault("blocks", []).append(
                {"backend": label, "block": i + 1, "wall_s": times[-1],
                 **comp})
        return roots

    with counting() as delta, flush_placements() as placed:
        bls.use_fastest()
        check(bls.backend_name() == "jax",
              f"use_fastest picked {bls.backend_name()!r}, not 'jax'")
        jax_roots = replay("jax")
    bls.use_native()
    native_roots = replay("native")
    for i, (a, b) in enumerate(zip(jax_roots, native_roots)):
        say(f"leg B block {i + 1} post-state root jax {a.hex()[:16]} "
            f"native {b.hex()[:16]}")
        check(a == b, f"leg B block {i + 1}: post-state roots differ")
    report["roots"] = [r.hex() for r in jax_roots]
    report["fallbacks"] = fallback_deltas(delta)
    check_after_leg("leg B", delta, {}, placed)


# ---------------------------------------------------------------------------
# Four chips: the mesh-sharded altair epoch at 2^20 validators
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def placement_devices():
    """Record the device set of every mesh placement."""
    from consensus_specs_tpu.parallel import mesh_state
    orig = mesh_state.place
    seen = []

    def recorded(host, mesh, pad_value=0):
        out = orig(host, mesh, pad_value)
        seen.append(frozenset(d.id for d in out.devices()))
        return out
    mesh_state.place = recorded
    try:
        yield seen
    finally:
        mesh_state.place = orig


def leg_mesh(clog, report, n_validators=1 << 20):
    import jax
    sys.path.insert(0, os.path.join(HERE, "benchmarks"))
    from bench_all import _synthetic_registry_state
    from consensus_specs_tpu.forks import build_spec
    from consensus_specs_tpu.parallel import mesh_epoch, mesh_state
    from consensus_specs_tpu.test_infra.metrics import counting
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.ssz import hash_tree_root

    n_dev = len(jax.devices())
    check(mesh_state.device_count() == n_dev >= 2,
          f"mesh sees {mesh_state.device_count()} of {n_dev} devices")
    bls.bls_active = False        # the epoch path verifies no signatures
    t0 = time.perf_counter()
    spec = build_spec("altair", "mainnet")
    pre = _synthetic_registry_state(spec, n_validators)
    report["build_s"] = time.perf_counter() - t0
    say(f"mesh: altair/mainnet registry of {n_validators} validators "
        f"built in {report['build_s']:.1f}s")
    boundary = int(pre.slot) + int(spec.SLOTS_PER_EPOCH) \
        - int(pre.slot) % int(spec.SLOTS_PER_EPOCH)

    def epoch(label):
        state = pre.copy()
        mark = clog.mark()
        with counting() as d:
            t0 = time.perf_counter()
            spec.process_slots(state, boundary)
            root = bytes(hash_tree_root(state))
            dt = time.perf_counter() - t0
        comp = clog.since(mark)
        say(f"mesh {label}: epoch transition + root {dt:.3f}s, "
            f"{comp['programs']} programs compiled "
            f"({comp['compile_s']:.1f}s compiling), root {root.hex()[:16]}")
        report.setdefault("runs", []).append(
            {"label": label, "wall_s": dt, **comp})
        return root, d

    try:
        mesh_state.use_mesh()
        with placement_devices() as placed:
            mesh_root, d_mesh = epoch("on, %d devices" % n_dev)
        mesh_state.use_fallback()
        single_root, d_single = epoch("off, 1 device")
    finally:
        mesh_state.use_auto()
    psums = {sub: d_mesh[f"mesh.psums{{site={sub}}}"]
             for sub in mesh_epoch.PSUM_BUDGET}
    spans = sorted({len(s) for s in placed})
    report.update(mesh_root=mesh_root.hex(), single_root=single_root.hex(),
                  psums=psums, psum_budget=dict(mesh_epoch.PSUM_BUDGET),
                  mesh_subtransitions=d_mesh["mesh.epoch{path=mesh}"],
                  mesh_merkle_builds=d_mesh["mesh.merkle{path=mesh}"],
                  placements=len(placed), placement_device_counts=spans,
                  fallbacks=fallback_deltas(d_mesh))
    say(f"mesh: psums {psums} (budget {dict(mesh_epoch.PSUM_BUDGET)}), "
        f"{report['mesh_subtransitions']} sub-transitions and "
        f"{report['mesh_merkle_builds']} merkle builds on the mesh, "
        f"{len(placed)} placements over {spans} devices")
    check(mesh_root == single_root, "mesh-on and mesh-off roots differ")
    check(psums == dict(mesh_epoch.PSUM_BUDGET),
          f"psum census {psums} != PSUM_BUDGET")
    check(report["mesh_subtransitions"] == 5,
          "not every altair sub-transition ran on the mesh")
    check(placed and spans == [n_dev],
          f"placements span {spans} devices, expected {n_dev}")
    check(not report["fallbacks"], f"mesh fell back: {report['fallbacks']}")
    check(d_single["mesh.epoch{path=mesh}"] == 0,
          "mesh-off run used the mesh")


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: legs A and B on one chip; 4: only the "
                         "mesh-sharded epoch, mesh on vs off")
    args = ap.parse_args(argv)
    setup_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    count = len(devices)
    say(f"device: {platform} {kind} x{count}; jax {jax.__version__}")
    clog = install_compile_log()
    report = {"device": {"platform": platform, "kind": kind,
                         "count": count}, "legs": {}}
    t_all = time.perf_counter()
    try:
        legs = ([("A", leg_a), ("B", leg_b)] if args.chips == 1
                else [("mesh", leg_mesh)])
        for name, fn in legs:
            leg = report["legs"][name] = {}
            t0 = time.perf_counter()
            fn(clog, leg)
            leg["wall_s"] = time.perf_counter() - t0
            say(f"leg {name} passed in {leg['wall_s']:.1f}s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        report["failure"] = str(e)
        return 1
    finally:
        report["wall_s"] = time.perf_counter() - t_all
        report["compile"] = clog.since(0)
        report["compile"]["trace_lower_s"] = clog.lower_s
        _write_report(report, args.chips)
    say(f"total {report['wall_s']:.1f}s; {report['compile']['programs']} "
        f"programs compiled in {report['compile']['compile_s']:.1f}s")
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


def _write_report(report, chips):
    out = os.path.join(HERE, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, "chip_smoke.json" if chips == 1
                        else f"chip_smoke_{chips}chips.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1, default=str)


if __name__ == "__main__":
    sys.exit(main())
