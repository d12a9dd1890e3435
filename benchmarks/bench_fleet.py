"""Fleet-simulator perf benchmark → ``BENCH_fleet.json`` (perf trajectory).

    PYTHONPATH=src python benchmarks/bench_fleet.py            # full
    PYTHONPATH=src python benchmarks/bench_fleet.py --quick    # CI smoke
    PYTHONPATH=src python benchmarks/bench_fleet.py --quick \
        --check BENCH_fleet.json                               # CI gate

Four measurements:

* **tick throughput** — the steady-workload fleet program's edge-ticks
  per second, with compile time split out (first call − steady call);
* **sweep wall-clock** — the registry × policies × seeds evaluation run
  the old way (one ``run_fleet`` per scenario/policy/seed, compiles
  amortized only across same-shape runs) vs the padded one-program batch
  (``run_registry_sweep``: a single jit for the whole sweep).  The
  reported ``speedup`` is the headline number of the one-program-sweeps
  PR (target ≥2×); both phases start from cleared compilation caches so
  each pays its honest compile bill;
* **flight-recorder cost** — trace-on vs trace-off ticks/sec (< 15 %
  overhead target), XLA backend-compile accounting, a retrace guard on
  the policy-generic tick program, and the paper's tail scoreboard
  (p50/p95/p99 deadline slack & completion latency, windowed p95/p99
  deadline-hit rates, per-task-type QoE frequencies) for rush-hour,
  cloud-crunch, and the stochastic duration-jitter / heavy-tail
  scenarios;
* **metropolis scaling** — edge-ticks/sec at ``--edges 64,256,1024``
  fleet sizes through the donated double-buffered replay, and the
  shape-bucketed sweep planner vs the padded single-program reference
  (speedup target ≥1.3×, summaries bitwise equal); see
  ``docs/SCALING.md``.

``BENCH_fleet.json`` keeps one section per mode (``quick`` / ``full``),
so a committed quick-mode baseline gates CI runs apples-to-apples while
the full section documents the real trajectory numbers.  ``--check``
compares ``ticks_per_sec`` against the committed baseline's same-mode
section and exits 1 on a >25 % regression (tune with ``--tolerance``).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

# the one-program batch shards its replica axis over every available
# core (the loop, running one mission at a time, cannot) — expose the
# cores as host devices before jax initializes
_CORES = os.cpu_count() or 1
if _CORES > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_CORES} "
        + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_fleet.json"


def _timed(fn) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def _clear_caches() -> None:
    from repro.sim import fleet_jax
    fleet_jax._fleet_program.cache_clear()
    jax.clear_caches()


def bench_throughput(quick: bool) -> dict:
    """Edge-ticks/sec of the steady paper workload (one compiled scan)."""
    from repro.core.task import PASSIVE, TABLE1
    from repro.sim.fleet_jax import default_signals, run_fleet

    models = [TABLE1[n] for n in PASSIVE]
    n_edges = 8 if quick else 16
    duration = 30_000.0 if quick else 120_000.0
    signals = default_signals(len(models), n_edges=n_edges,
                              duration_ms=duration)
    _clear_caches()
    run = lambda: run_fleet(models, "DEMS-A", signals)  # noqa: E731
    first = _timed(run)
    steady = min(_timed(run) for _ in range(2 if quick else 3))
    n_ticks = int(signals.times.shape[0])
    return dict(
        n_edges=n_edges, n_ticks=n_ticks, policy="DEMS-A",
        compile_s=round(first - steady, 3), wall_s=round(steady, 3),
        ticks_per_sec=round(n_ticks / steady, 1),
        edge_ticks_per_sec=round(n_ticks * n_edges / steady, 1))


def bench_sweep(quick: bool) -> dict:
    """Registry sweep: per-scenario loop vs the padded one-program batch."""
    from repro.scenarios import (fleet_summary, get, names,
                                 run_registry_sweep, run_scenario_fleet)

    duration = 10_000.0 if quick else 45_000.0
    policies = ("EDF-E+C", "DEMS", "DEMS-A") if quick else \
        ("EDF-E+C", "DEMS", "DEMS-A", "GEMS", "GEMS-COOP")
    seeds = (0, 1) if quick else (0, 1, 2)
    scenarios = names()

    _clear_caches()
    t0 = time.perf_counter()
    loop_rows = []
    for sc in scenarios:
        for pol in policies:
            for seed in seeds:
                spec = get(sc, duration_ms=duration, seed=seed)
                loop_rows.append(fleet_summary(
                    run_scenario_fleet(spec, pol)))
    loop_s = time.perf_counter() - t0

    _clear_caches()
    t0 = time.perf_counter()
    batch_rows = run_registry_sweep(scenarios, policies, seeds,
                                    duration_ms=duration, mesh="auto")
    batch_s = time.perf_counter() - t0

    mismatches = sum(
        any(row[k] != batch[k] for k in row)
        for row, batch in zip(loop_rows, batch_rows))
    return dict(
        n_runs=len(batch_rows), n_scenarios=len(scenarios),
        policies=list(policies), seeds=list(seeds),
        duration_ms=duration, batch_devices=jax.device_count(),
        loop_wall_s=round(loop_s, 2), batch_wall_s=round(batch_s, 2),
        speedup=round(loop_s / batch_s, 2), loop_vs_batch_mismatches=
        mismatches)


def bench_trace(quick: bool) -> dict:
    """Flight-recorder cost + the paper's tail scoreboard.

    Measures trace-on vs trace-off ticks/sec on the same steady
    workload as :func:`bench_throughput` (< 15 % overhead target — the
    trace-off program is bit-identical to pre-recorder, so only the
    trace-on number can move), counts real XLA backend compiles while
    both programs build, and verifies the tick program stayed
    policy-generic (one jit trace per cached program).  Also records
    p50/p95/p99 deadline-slack / completion-latency, windowed p95/p99
    deadline-hit rates, and per-task-type QoE frequencies for the
    rush-hour, cloud-crunch, duration-jitter, and heavy-tail scenarios.
    """
    from repro.core.task import PASSIVE, TABLE1
    from repro.obs import TraceSpec, metrics
    from repro.obs.prof import (CompileCounter, fleet_compile_stats,
                                reset_fleet_programs)
    from repro.scenarios import get, run_scenario_fleet
    from repro.sim.fleet_jax import default_signals, run_fleet

    models = [TABLE1[n] for n in PASSIVE]
    n_edges = 8 if quick else 16
    duration = 30_000.0 if quick else 120_000.0
    signals = default_signals(len(models), n_edges=n_edges,
                              duration_ms=duration)
    tspec = TraceSpec.full()
    reset_fleet_programs()
    jax.clear_caches()
    off = lambda: run_fleet(models, "DEMS-A", signals)          # noqa: E731
    on = lambda: run_fleet(models, "DEMS-A", signals,           # noqa: E731
                           trace=tspec)
    with CompileCounter() as cc:
        _timed(off)
        _timed(on)
    reps = 3 if quick else 5
    off_s = min(_timed(off) for _ in range(reps))
    on_s = min(_timed(on) for _ in range(reps))
    stats = fleet_compile_stats()
    n_ticks = int(signals.times.shape[0])

    tails = {}
    tail_duration = 15_000.0 if quick else 45_000.0
    for sc in ("rush-hour", "cloud-crunch", "duration-jitter",
               "heavy-tail"):
        spec = get(sc, duration_ms=tail_duration)
        res = run_scenario_fleet(spec, "DEMS-A", trace=tspec)
        metrics.check_conservation(res.counters)
        tm = metrics.tail_metrics(res.counters, tspec,
                                  list(spec.model_names))
        tails[sc] = dict(
            hit_rate=round(tm["hit_rate"], 4),
            deadline_hit={k: round(v, 4) if isinstance(v, float) else v
                          for k, v in tm["deadline_hit"].items()},
            slack_ms={p: round(v, 1) for p, v in tm["slack_ms"].items()},
            latency_ms={p: round(v, 1)
                        for p, v in tm["latency_ms"].items()},
            qoe_frequency={k: round(v, 4)
                           for k, v in tm["qoe_frequency"].items()},
            drops_by_cause=tm["drops_by_cause"])
    return dict(
        n_edges=n_edges, n_ticks=n_ticks, policy="DEMS-A",
        ticks_per_sec_off=round(n_ticks / off_s, 1),
        ticks_per_sec_on=round(n_ticks / on_s, 1),
        overhead_frac=round(on_s / off_s - 1.0, 4),
        backend_compiles=cc.count,
        compile_secs=round(cc.total_secs, 2),
        programs=stats.programs,
        max_traces_per_program=stats.max_traces_per_program,
        policy_generic=stats.policy_generic,
        tails=tails)


def bench_scaling(quick: bool, edges: tuple[int, ...]) -> dict:
    """Metropolis-scale section: edge-ticks/sec vs fleet size, plus the
    shape-bucketed sweep planner vs the padded reference.

    Two axes, both with bitwise parity guards:

    * **fleet-size scaling** — the steady workload at each ``--edges``
      size through the donated double-buffered replay
      (``run_fleet(donate=True, chunk_ticks=…)``), reporting
      edge-ticks/sec per size (target: near-linear growth) and checking
      the donated path equals the plain whole-horizon scan bitwise;
    * **registry sweep** — the full registry × the acceptance policy
      set, bucketed planner (donation on, per-bucket auto mesh) vs the
      padded single-program baseline, reporting the steady-state
      (warm-cache) wall-clock speedup (target ≥1.3×) with each
      planner's one-off compile bill split out, and counting summary
      mismatches (must be 0).
    """
    import numpy as np

    from repro.core.task import PASSIVE, TABLE1
    from repro.scenarios import run_registry_sweep
    from repro.sim.fleet_jax import default_signals, run_fleet

    models = [TABLE1[n] for n in PASSIVE]
    duration = 5_000.0 if quick else 10_000.0
    chunk = 64
    reps = 2
    rows = []
    for n_edges in edges:
        signals = default_signals(len(models), n_edges=n_edges,
                                  duration_ms=duration)
        _clear_caches()
        run = lambda: run_fleet(models, "DEMS-A", signals,   # noqa: E731
                                donate=True, chunk_ticks=chunk)
        first = _timed(run)
        steady = min(_timed(run) for _ in range(reps))
        n_ticks = int(signals.times.shape[0])
        rows.append(dict(
            n_edges=n_edges, n_ticks=n_ticks,
            compile_s=round(first - steady, 3), wall_s=round(steady, 3),
            ticks_per_sec=round(n_ticks / steady, 1),
            edge_ticks_per_sec=round(n_ticks * n_edges / steady, 1)))

    # donation parity at the smallest size: the donated double-buffered
    # replay must equal the plain whole-horizon scan bitwise
    sig0 = default_signals(len(models), n_edges=min(edges),
                           duration_ms=duration)
    plain = run_fleet(models, "DEMS-A", sig0)
    donated = run_fleet(models, "DEMS-A", sig0, donate=True,
                        chunk_ticks=chunk)
    parity_ok = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(donated)))

    # sweep planners: first call from cleared caches pays the compile
    # bill (reported split out — the bucketed planner traces one
    # program per shape bucket, the padded reference exactly one), the
    # steady call is the metropolis regime where long missions amortize
    # compiles to zero; the headline speedup compares steady walls
    policies = ("DEMS-A", "GEMS-B", "GEMS-COOP")
    seeds = (0,) if quick else (0, 1)
    sweep_duration = 10_000.0 if quick else 20_000.0

    def timed_sweep(planner, donate):
        _clear_caches()
        t0 = time.perf_counter()
        swept = run_registry_sweep(
            policies=policies, seeds=seeds, duration_ms=sweep_duration,
            mesh="auto", planner=planner, donate=donate)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_registry_sweep(
            policies=policies, seeds=seeds, duration_ms=sweep_duration,
            mesh="auto", planner=planner, donate=donate)
        steady = time.perf_counter() - t0
        return swept, first, steady

    bucketed_rows, bucketed_first, bucketed_s = timed_sweep(
        "bucketed", donate=True)
    padded_rows, padded_first, padded_s = timed_sweep(
        "padded", donate=False)
    mismatches = sum(
        any(b[k] != p[k] for k in b)
        for b, p in zip(bucketed_rows, padded_rows))
    return dict(
        policy="DEMS-A", duration_ms=duration, chunk_ticks=chunk,
        donation_parity_ok=parity_ok, edges=rows,
        sweep=dict(
            n_runs=len(bucketed_rows), policies=list(policies),
            seeds=list(seeds), duration_ms=sweep_duration,
            devices=jax.device_count(),
            bucketed_wall_s=round(bucketed_s, 2),
            bucketed_compile_s=round(bucketed_first - bucketed_s, 2),
            padded_wall_s=round(padded_s, 2),
            padded_compile_s=round(padded_first - padded_s, 2),
            speedup_vs_padded=round(padded_s / bucketed_s, 2),
            mismatches=mismatches))


def check(report: dict, baseline_path: pathlib.Path,
          tolerance: float) -> int:
    mode = "quick" if report["quick"] else "full"
    baseline = json.loads(baseline_path.read_text()).get(mode)
    if baseline is None:
        print(f"FAIL: baseline {baseline_path} has no {mode!r} section")
        return 1
    if "throughput" in report:
        want = baseline["throughput"]["ticks_per_sec"]
        got = report["throughput"]["ticks_per_sec"]
        floor = (1.0 - tolerance) * want
        print(f"ticks/sec: current {got}, baseline {want} "
              f"(floor {floor:.1f} at {tolerance:.0%} tolerance)")
        if got < floor:
            print("FAIL: per-tick throughput regressed beyond tolerance — "
                  "if intentional, regenerate BENCH_fleet.json")
            return 1
    if report.get("sweep", {}).get("loop_vs_batch_mismatches"):
        print("FAIL: one-program sweep summaries diverge from the "
              "per-scenario loop")
        return 1
    trace = report.get("trace")
    if trace is not None:
        print(f"trace overhead: {trace['overhead_frac']:+.1%} "
              f"({trace['ticks_per_sec_on']} on vs "
              f"{trace['ticks_per_sec_off']} off ticks/sec)")
        if not trace["policy_generic"]:
            print("FAIL: tick program retraced across policies "
                  "(PolicyParams leaked into a static argument)")
            return 1
    scaling = report.get("scaling")
    if scaling is not None:
        # exactness gates are hardware-free: the bucketed planner and
        # the donated replay must reproduce the padded reference bitwise
        if scaling["sweep"]["mismatches"]:
            print("FAIL: bucketed sweep summaries diverge from the "
                  "padded reference path")
            return 1
        if not scaling["donation_parity_ok"]:
            print("FAIL: donated double-buffered replay diverged from "
                  "the plain scan")
            return 1
        print(f"scaling: bucketed sweep "
              f"{scaling['sweep']['speedup_vs_padded']}x vs padded, "
              f"parity OK")
    print("OK")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="short missions / fewer reps (CI smoke)")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    ap.add_argument("--check", type=pathlib.Path, default=None,
                    help="baseline BENCH_fleet.json to gate against")
    ap.add_argument("--report", type=pathlib.Path, default=None,
                    help="with --check: gate a previously written report "
                    "file instead of re-measuring")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional ticks/sec regression")
    ap.add_argument("--edges", default=None,
                    help="comma-separated fleet sizes for the scaling "
                    "section (default: 64 quick, 64,256,1024 full)")
    ap.add_argument("--scaling-only", action="store_true",
                    help="measure only the scaling section and merge it "
                    "into the mode section in place (CI scaling-smoke)")
    args = ap.parse_args()

    if args.check is not None and args.report is not None:
        mode = "quick" if args.quick else "full"
        report = json.loads(args.report.read_text())[mode]
        sys.exit(check(report, args.check, args.tolerance))

    edges = tuple(int(x) for x in args.edges.split(",")) if args.edges \
        else ((64,) if args.quick else (64, 256, 1024))
    if args.scaling_only:
        report = dict(
            quick=args.quick,
            jax=jax.__version__, backend=jax.default_backend(),
            devices=jax.device_count(), cpus=os.cpu_count(),
            scaling=bench_scaling(args.quick, edges))
    else:
        report = dict(
            quick=args.quick,
            jax=jax.__version__, backend=jax.default_backend(),
            devices=jax.device_count(), cpus=os.cpu_count(),
            throughput=bench_throughput(args.quick),
            sweep=bench_sweep(args.quick),
            trace=bench_trace(args.quick),
            scaling=bench_scaling(args.quick, edges))
    print(json.dumps(report, indent=1))
    if args.check is not None:
        sys.exit(check(report, args.check, args.tolerance))
    merged = json.loads(args.out.read_text()) if args.out.exists() else {}
    mode = "quick" if args.quick else "full"
    if args.scaling_only:
        # refresh only the scaling subsection; sibling sections (and
        # their committed baselines) stay untouched
        merged.setdefault(mode, {})["scaling"] = report["scaling"]
    else:
        merged[mode] = report
    args.out.write_text(json.dumps(merged, indent=1, sort_keys=True) + "\n")
    print("wrote", args.out)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
