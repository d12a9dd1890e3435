"""Online-controller latency benchmark → ``controller`` section of
``BENCH_fleet.json``.

    PYTHONPATH=src python benchmarks/bench_serve.py            # full
    PYTHONPATH=src python benchmarks/bench_serve.py --quick    # CI smoke

Floods a :class:`repro.serve.controller.FleetController` with a
synthetic arrival storm — every (edge, model) cell of every tick
occupied, the densest signal the window builder can emit — and measures
the two latencies that bound the online control plane:

* **per-tick decision latency** — wall-clock of each jitted
  ``step_chunk`` window divided by its tick count (p50/p95/p99 over the
  run, warmup window excluded so the one-off compile is reported
  separately);
* **ingest-to-decision lag** — wall-clock from a tick's first
  ``submit()`` to the window step that scheduled it, as driven by a
  virtual-time :meth:`poll` cadence of one window.

The section lands next to ``throughput``/``sweep``/``trace`` in the
committed baseline (same ``quick``/``full`` mode split), so the serve
layer's latency trajectory is tracked alongside the simulator's
throughput.  ``--check`` gates on p95 per-tick latency regressing >2×
against the committed same-mode section (wall-clock tails on shared CI
runners are noisy; the gate is a guardrail against order-of-magnitude
rot, not a 25 % throughput gate like ``bench_fleet.py``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_fleet.json"


def _pcts(samples) -> dict:
    a = np.asarray(samples, dtype=np.float64)
    if a.size == 0:
        return {f"p{q:g}": None for q in (50, 95, 99)}
    return {f"p{q:g}": round(float(np.percentile(a, q)), 4)
            for q in (50, 95, 99)}


def bench_controller(*, policy: str = "DEMS-A", n_edges: int = 4,
                     window_ticks: int = 8, duration_ms: float = 30_000.0,
                     dt: float = 25.0) -> dict:
    """Arrival-flood latency profile of one controller configuration."""
    from repro.scenarios.registry import get
    from repro.serve.controller import FleetController

    models = get("baseline").models
    ctl = FleetController(models, policy, n_edges=n_edges, dt=dt,
                          window_ticks=window_ticks)

    def flood(lo_ms: float, hi_ms: float) -> None:
        # worst-case storm: every (edge, model) cell of every tick fires
        t = lo_ms
        while t < hi_ms:
            for e in range(n_edges):
                for m in range(len(models)):
                    ctl.submit(t, e, m)
            t += dt

    # warmup: one window through the jit cache, timed as the compile bill
    w_ms = window_ticks * dt
    flood(0.0, w_ms)
    t0 = time.perf_counter()
    ctl.poll(w_ms)
    compile_s = time.perf_counter() - t0
    ctl.reset_latency_stats()

    now = w_ms
    while now < duration_ms:
        flood(now, now + w_ms)
        now += w_ms
        ctl.poll(now)
    ctl.close()

    steps = np.asarray(ctl.step_latencies_ms)
    snap = ctl.metrics_snapshot()
    return dict(
        policy=policy, n_edges=n_edges, n_models=len(models),
        window_ticks=window_ticks, dt_ms=dt,
        duration_ms=duration_ms, windows=int(ctl.windows_run),
        arrivals=int(snap["completed"] + snap["missed"] + snap["dropped"]),
        compile_s=round(compile_s, 3),
        per_tick_ms=_pcts(steps / window_ticks),
        step_ms=_pcts(steps),
        ingest_to_decision_ms={
            k: None if v is None else round(v, 4)
            for k, v in snap["ingest_to_decision_ms"].items()},
        completion_rate=round(snap["completion_rate"], 4))


def bench_backpressure(*, policy: str = "DEMS-A", n_edges: int = 2,
                       dt: float = 25.0, max_pending_ticks: int = 64,
                       n_submit: int = 5_000) -> dict:
    """Bounded-ingest stress: flood far past the pending bound with no
    polling at all and prove the controller sheds instead of growing
    without bound or deadlocking — every submission returns, accepted +
    shed accounts for all of them, and the buffer never exceeds the
    configured bound."""
    from repro.scenarios.registry import get
    from repro.serve.controller import FleetController

    models = get("baseline").models
    ctl = FleetController(models, policy, n_edges=n_edges, dt=dt,
                          max_pending_ticks=max_pending_ticks,
                          shed_policy="reject")
    t0 = time.perf_counter()
    accepted = 0
    for i in range(n_submit):
        accepted += ctl.submit(i * dt, i % n_edges, i % len(models)) >= 0
    wall_s = time.perf_counter() - t0
    return dict(max_pending_ticks=max_pending_ticks, submitted=n_submit,
                accepted=int(accepted), shed=int(ctl.shed_tasks),
                pending_ticks=int(ctl.builder.pending_ticks),
                wall_s=round(wall_s, 3))


def check_gate(section: dict, baseline_path, mode: str) -> int:
    """The ``--check`` CI gate as a testable function (exit-code style).

    Fails (returns 1) when p95 per-tick latency regressed >2× against
    the committed same-mode ``controller`` baseline, or when the
    bounded-backpressure invariants are violated: the ingest flood must
    be shed (not buffered unboundedly) and fully accounted for — a hang
    would never reach here, a leak shows up as accepted + shed != sent.
    """
    base = json.load(open(baseline_path)).get(mode, {}).get("controller")
    if base and base["per_tick_ms"]["p95"]:
        ratio = section["per_tick_ms"]["p95"] / base["per_tick_ms"]["p95"]
        print(f"p95 per-tick {section['per_tick_ms']['p95']} ms vs "
              f"baseline {base['per_tick_ms']['p95']} ms "
              f"({ratio:.2f}x)")
        if ratio > 2.0:
            print("FAIL: controller p95 per-tick latency regressed >2x")
            return 1
    else:
        print(f"no {mode}.controller baseline in {baseline_path}; skipped")
    bp = section["backpressure"]
    ok = (bp["shed"] > 0
          and bp["accepted"] + bp["shed"] == bp["submitted"]
          and bp["pending_ticks"] <= bp["max_pending_ticks"])
    print(f"backpressure: {bp['accepted']} accepted / {bp['shed']} "
          f"shed of {bp['submitted']}, "
          f"{bp['pending_ticks']}/{bp['max_pending_ticks']} "
          f"ticks pending")
    if not ok:
        print("FAIL: bounded-backpressure invariant violated")
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="short flood (CI smoke): 2 edges, 10 s mission")
    ap.add_argument("--policy", default="DEMS-A")
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="BENCH json to merge the controller section into")
    ap.add_argument("--no-write", action="store_true",
                    help="print the section, leave the json untouched")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="gate: fail if p95 per-tick latency regressed "
                         ">2x vs this baseline's same-mode section")
    args = ap.parse_args(argv)

    kw = (dict(n_edges=2, duration_ms=10_000.0) if args.quick
          else dict(n_edges=4, duration_ms=30_000.0))
    section = bench_controller(policy=args.policy, **kw)
    section["backpressure"] = bench_backpressure(policy=args.policy)
    mode = "quick" if args.quick else "full"
    print(json.dumps({mode: {"controller": section}}, indent=2))

    if args.check:
        rc = check_gate(section, args.check, mode)
        if rc:
            return rc

    if not args.no_write:
        path = pathlib.Path(args.out)
        data = json.load(open(path)) if path.exists() else {}
        data.setdefault(mode, {})["controller"] = section
        path.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")
        print(f"wrote {mode}.controller -> {path}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
