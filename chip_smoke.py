#!/usr/bin/env python3
"""The scheduler's main path on a TPU, end to end, with hard checks.

    python3 chip_smoke.py             # one chip: every one-chip phase
    python3 chip_smoke.py --chips 4   # four chips: the sharded paths only

One process, no fallback.  It exits non-zero, and prints no result line,
when JAX finds no TPU, when the repository's ``src/`` is missing, or when
any phase fails.  One chip runs, in order:

* ``kernels``    the selection kernel (``kernels.sched_ops``) against the
                 jnp reference, bitwise, at the fleet tick's call shapes,
                 and the Mosaic kernel present in each compiled program;
* ``registry``   all 14 registry scenarios x 4 policies through the
                 bucketed sweep planner against the golden summaries, and
                 the exact conservation ledger of a traced run;
* ``metropolis`` 1024-edge DEMS-A and 256-edge DEMS-COOP replays, donated
                 and chunked, bitwise against the whole-horizon scan;
* ``controller`` the streaming ``FleetController`` on 64 edges, bitwise
                 against replay, and a checkpoint restore resuming bitwise.

``--chips 4`` runs the registry sweep with ``mesh="auto"``, the 2-D
``(replica, edge)`` batch and a 1024-edge DEMS-COOP fleet on a 1-D edge
mesh, each bitwise against the same run unsharded.

Every line but the last is information; the last line is one JSON object
``{"ok": true, "device": {...}}``.  The persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` beside this file.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.task import PASSIVE, TABLE1  # noqa: E402
from repro.kernels import ref, sched_ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.obs import metrics  # noqa: E402
from repro.obs.prof import CompileCounter  # noqa: E402
from repro.obs.trace import TraceSpec  # noqa: E402
from repro.scenarios import (DroneSpec, EdgeSite, ScenarioSpec,  # noqa: E402
                             compile_fleet, compile_registry_batch, get,
                             run_registry_sweep, run_scenario_fleet)
from repro.scenarios.runner import assert_streaming_equivalence  # noqa: E402
from repro.serve.controller import FleetController  # noqa: E402
from repro.sim.fleet_jax import (EDGE_CAP, CLOUD_CAP,  # noqa: E402
                                 FleetPolicy, FleetProgram, Profiles,
                                 default_signals, run_batch, run_fleet,
                                 slice_signals)

ROOT = pathlib.Path(__file__).resolve().parent
GOLDEN = ROOT / "tests" / "golden" / "fleet_summaries.json"
GOLDEN_POLICIES = ("DEMS", "GEMS-COOP", "SJF-E+C", "GEMS-B")
MODELS = [TABLE1[n] for n in PASSIVE]
DT = 25.0
CHUNK = 64
REPLAY_MS = 10_000.0                  # 400 ticks
REPLAYS = (("DEMS-A", 1024), ("DEMS-COOP", 256))
CONTROLLER_EDGES = 64
EDGE_MESH_EDGES = 1024


def say(*parts) -> None:
    print(*parts, flush=True)


def bitwise_diff(a, b) -> list[int]:
    """Indices of the leaves of two trees that differ bitwise."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        raise AssertionError(f"tree sizes differ: {len(la)} vs {len(lb)}")
    return [i for i, (x, y) in enumerate(zip(la, lb))
            if not np.array_equal(np.asarray(x), np.asarray(y))]


def assert_bitwise(a, b, what: str) -> None:
    bad = bitwise_diff(a, b)
    if bad:
        raise AssertionError(f"{what}: {len(bad)} leaves differ "
                             f"(first at leaf {bad[0]})")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def _argext_cases(rng):
    """(label, is_max, scores, mask, vmapped) at the tick's call shapes:
    ``steal_select`` (vmapped over edges, CLOUD_CAP scores),
    ``export_select`` (vmapped, EDGE_CAP) and ``peer_offload`` (one row of
    E loads), plus unbatched row counts around the 8-row tile."""
    ranks = np.asarray([0.57, 0.43, 0.35, -0.012], np.float32)
    for rows in (1, 3, 8, 16, 1024):
        s = np.round(rng.normal(size=(rows, 64)) * 4).astype(np.float32)
        m = rng.random((rows, 64)) < 0.4
        m[0] = False                                 # an all-masked row
        yield f"rows={rows}", bool(rows % 2), s, m, False
    for e in (64, 1024):
        s = ranks[rng.integers(0, 4, (e, CLOUD_CAP))] \
            + np.where(rng.random((e, CLOUD_CAP)) < 0.3, 1e12, 0.0)
        yield (f"steal E={e}", True, s.astype(np.float32),
               rng.random((e, CLOUD_CAP)) < 0.5, True)
        s = rng.normal(0, 400.0, (e, EDGE_CAP)).astype(np.float32)
        s[rng.random((e, EDGE_CAP)) < 0.4] = sched_ops.POS
        yield (f"export E={e}", False, s, rng.random((e, EDGE_CAP)) < 0.3,
               True)
    for e in (2, 64, 256, 1024):
        s = np.abs(rng.normal(500.0, 300.0, e)).astype(np.float32)
        s[rng.random(e) < 0.2] = sched_ops.POS
        yield f"peer E={e}", False, s, rng.random(e) < 0.9, False


def phase_kernels() -> None:
    rng = np.random.default_rng(0x5eed)
    for label, is_max, s, m, vmapped in _argext_cases(rng):
        def kernel(s, m, is_max=is_max):
            return sched_ops.masked_argext(s, m, is_max=is_max)

        def want(s, m, is_max=is_max):
            return ref.ref_masked_argext(s, m, is_max=is_max)
        if vmapped:
            kernel, want = jax.vmap(kernel), jax.vmap(want)
        args = (jnp.asarray(s), jnp.asarray(m))
        compiled = jax.jit(kernel).lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{label}: no Mosaic kernel in the program")
        assert_bitwise(compiled(*args), jax.jit(want)(*args),
                       f"kernel vs reference at {label} {s.shape}")
    say("  kernel == reference bitwise at every call shape")


def phase_registry() -> None:
    golden = json.loads(GOLDEN.read_text())
    rows = run_registry_sweep(None, GOLDEN_POLICIES, (0,),
                              duration_ms=45_000.0, dt=DT)
    if len(rows) != len(golden) * len(GOLDEN_POLICIES):
        raise AssertionError(f"{len(rows)} rows for {len(golden)} scenarios")
    fails, drifts = [], 0
    for row in rows:
        want = golden[row["scenario"]][row["policy"]]
        for key, g in want.items():
            got = row[key]
            # tests/test_fleet_batch.py's bound, and the stricter staleness
            # bound of tests/golden/regen_fleet_summaries.py
            tol = 0.02 if key == "completion_rate" \
                else max(3.0, 0.05 * abs(g))
            where = f"{row['scenario']}/{row['policy']}/{key}"
            if abs(got - g) > max(1.5, 5e-3 * abs(g)):
                drifts += 1
                say(f"  drift {where}: got {got}, golden {g}")
            if abs(got - g) > tol:
                fails.append(f"{where}: got {got}, golden {g} (±{tol:.3g})")
    say(f"  {len(rows)} sweep rows, {drifts} values past the strict bound")
    if fails:
        raise AssertionError("golden mismatch: " + "; ".join(fails))
    res = run_scenario_fleet(get("cloud-crunch", duration_ms=45_000.0),
                             "DEMS-A", dt=DT, trace=TraceSpec(counters=True))
    metrics.check_conservation(res.counters)
    led = metrics.conservation_ledger(res.counters)
    say(f"  cloud-crunch DEMS-A ledger exact: arrived "
        f"{int(led['arrived'][-1])} = settled {int(led['settled'][-1])} "
        f"+ in flight {int(led['in_flight'][-1])}")


def _replay(policy: str, n_edges: int) -> None:
    sig = default_signals(len(MODELS), n_edges=n_edges,
                          duration_ms=REPLAY_MS, dt=DT)
    n_ticks = int(sig.times.shape[0])
    plain = run_fleet(MODELS, policy, sig, dt=DT)
    donated = run_fleet(MODELS, policy, sig, dt=DT, donate=True,
                        chunk_ticks=CHUNK)
    assert_bitwise(donated, plain, f"{policy} x{n_edges} donated vs plain")
    t0 = time.perf_counter()
    jax.block_until_ready(run_fleet(MODELS, policy, sig, dt=DT, donate=True,
                                    chunk_ticks=CHUNK))
    wall = time.perf_counter() - t0
    pol = FleetPolicy.from_name(policy)
    prog = FleetProgram.for_policy(pol, dt=DT, donate=True)
    prof = Profiles.build(MODELS)
    mem = prog.lower(prof, pol.params(), prog.init(prof, pol, n_edges),
                     slice_signals(sig, 0, CHUNK)).compile().memory_analysis()
    say(f"  {policy} {n_edges} edges x {n_ticks} ticks: donated == plain "
        f"bitwise; {n_ticks * n_edges / wall:.1f} edge-ticks/s warm "
        f"({wall:.3f} s); chunk program bytes: argument "
        f"{mem.argument_size_in_bytes}, output {mem.output_size_in_bytes}, "
        f"alias {mem.alias_size_in_bytes}, temp {mem.temp_size_in_bytes}, "
        f"code {mem.generated_code_size_in_bytes}")


def phase_metropolis() -> None:
    for policy, n_edges in REPLAYS:
        _replay(policy, n_edges)


def grid_spec(n_edges: int, duration_ms: float) -> ScenarioSpec:
    """``n_edges`` disjoint coverage zones, three hovering drones each."""
    return ScenarioSpec(
        name=f"grid-{n_edges}", duration_ms=duration_ms,
        edges=tuple(EdgeSite(3_000.0 * e, 0.0) for e in range(n_edges)),
        drones=tuple(DroneSpec(waypoints=((3_000.0 * e, 100.0 * d),))
                     for e in range(n_edges) for d in range(3)))


def phase_controller() -> None:
    spec = grid_spec(CONTROLLER_EDGES, REPLAY_MS)
    window = 16
    s = assert_streaming_equivalence(spec, "DEMS-A", dt=DT,
                                     window_ticks=window)
    say(f"  {spec.n_edges}-edge stream == replay bitwise ({s['completed']} completed)")
    sig = compile_fleet(spec, DT)
    n_ticks = int(sig.times.shape[0])
    kill = (n_ticks // window // 2) * window
    kw = dict(n_edges=spec.n_edges, dt=DT, window_ticks=window,
              cloud_slots=spec.cloud_concurrency)

    def feed(ctl, lo, hi):
        for t in range(lo, hi, window):
            ctl.step_signals(slice_signals(sig, t, min(t + window, hi)))

    whole = FleetController(spec.models, "DEMS-A", **kw)
    feed(whole, 0, n_ticks)
    with tempfile.TemporaryDirectory() as tmp:
        path = str(pathlib.Path(tmp) / "ckpt")
        first = FleetController(spec.models, "DEMS-A", checkpoint_path=path,
                                **kw)
        feed(first, 0, kill)
        first.checkpoint()
        resumed = FleetController(spec.models, "DEMS-A",
                                  checkpoint_path=path, **kw)
        if resumed.restore() != kill:
            raise AssertionError("restore returned the wrong tick")
        feed(resumed, kill, n_ticks)
    assert_bitwise(resumed.state, whole.state, "restored vs uninterrupted")
    say(f"  restore at tick {kill} of {n_ticks} resumes bitwise")


ONE_CHIP = (("kernels", phase_kernels), ("registry", phase_registry),
            ("metropolis", phase_metropolis),
            ("controller", phase_controller))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_sweep_auto() -> None:
    kw = dict(duration_ms=10_000.0, dt=DT)
    pols = ("DEMS", "GEMS-COOP")
    ref_rows = run_registry_sweep(None, pols, (0, 1), **kw)
    t0 = time.perf_counter()
    rows = run_registry_sweep(None, pols, (0, 1), mesh="auto", **kw)
    wall = time.perf_counter() - t0
    bad = [i for i, (a, b) in enumerate(zip(rows, ref_rows)) if a != b]
    if bad or len(rows) != len(ref_rows):
        raise AssertionError(f"mesh='auto' rows differ at {bad[:5]}")
    say(f"  registry sweep mesh='auto' == unsharded: {len(rows)} rows "
        f"bitwise ({wall:.2f} s with compile)")


def phase_batch_2d() -> None:
    batch, rows = compile_registry_batch(
        ("baseline", "rush-hour"), ("DEMS", "DEMS-COOP"), (0, 1),
        duration_ms=8_000.0, dt=DT)
    want = run_batch(batch, dt=DT)
    got = run_batch(batch, dt=DT, mesh=make_mesh((2, 2), ("replica", "edge")))
    assert_bitwise(got, want, "2-D (replica, edge) run_batch")
    say(f"  2-D (replica, edge) run_batch == unsharded: {len(rows)} runs "
        f"bitwise")


def phase_edge_mesh() -> None:
    n = len(jax.devices())
    sig = default_signals(len(MODELS), n_edges=EDGE_MESH_EDGES,
                          duration_ms=REPLAY_MS, dt=DT)
    want = run_fleet(MODELS, "DEMS-COOP", sig, dt=DT)
    t0 = time.perf_counter()
    got = jax.block_until_ready(run_fleet(
        MODELS, "DEMS-COOP", sig, dt=DT, mesh=make_mesh((n,), ("edge",))))
    wall = time.perf_counter() - t0
    assert_bitwise(got, want, f"DEMS-COOP on a {n}-chip edge mesh")
    say(f"  {EDGE_MESH_EDGES}-edge DEMS-COOP edge-sharded over {n} chips == unsharded "
        f"bitwise ({wall:.2f} s with compile)")


FOUR_CHIPS = (("sweep-auto", phase_sweep_auto), ("batch-2d", phase_batch_2d),
              ("edge-mesh", phase_edge_mesh))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    dev = dict(platform=devices[0].platform, kind=devices[0].device_kind,
               count=len(devices))
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']} jax={jax.__version__}")
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing ran", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {dev['count']}", file=sys.stderr)
        return 2
    say(f"compile cache: {enable_compile_cache()}")
    failed = []
    t_all = time.perf_counter()
    with CompileCounter() as cc:
        for name, phase in ONE_CHIP if args.chips == 1 else FOUR_CHIPS:
            t0, n0, s0 = time.perf_counter(), cc.count, cc.total_secs
            say(f"phase {name}:")
            try:
                phase()
            except Exception:  # noqa: BLE001 - report, run the rest
                failed.append(name)
                traceback.print_exc()
                say(f"phase {name}: FAILED")
                continue
            say(f"phase {name}: ok in {time.perf_counter() - t0:.2f} s "
                f"({cc.count - n0} compiles, "
                f"{cc.total_secs - s0:.2f} s compiling)")
    say(f"total: {time.perf_counter() - t_all:.2f} s, {cc.count} backend "
        f"compiles, {cc.total_secs:.2f} s compiling")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
