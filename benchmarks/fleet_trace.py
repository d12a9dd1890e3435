"""Flight-recorder CLI: decision timeline + tail table for any run.

Runs one registry scenario under one fleet policy with the full
:class:`~repro.obs.trace.TraceSpec` and renders what the compiled tick
program decided, tick by tick — admissions, dispatches, drops by cause,
steals/migrations/peer offloads, queue depths — plus the paper's tail
scoreboard (p50/p95/p99 deadline slack and completion latency, windowed
p95/p99 deadline-hit rates, per-task-type QoE success frequencies).

    PYTHONPATH=src python benchmarks/fleet_trace.py \\
        --scenario cloud-crunch --policy DEMS-A --duration-ms 20000
    PYTHONPATH=src python benchmarks/fleet_trace.py --scenario rush-hour \\
        --policy GEMS-COOP --json trace.json --perfetto trace.pftrace.json

``--json``/``--csv`` dump the full per-tick series
(:func:`repro.obs.metrics.to_json` / ``to_csv``); ``--perfetto`` writes
a Chrome/Perfetto counter-track stream for ``ui.perfetto.dev``.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.obs import TraceSpec, metrics
from repro.scenarios import get, names, run_scenario_fleet


def timeline(ts: dict, dt: float, *, width: int = 12) -> str:
    """An aggregated per-window decision timeline (text)."""
    n = len(ts["arrivals"])
    win = max(1, n // width)
    cols = ("arrivals", "admit_edge", "admit_cloud", "edge_exec",
            "cloud_dispatch", "pool_blocked", "hit", "miss", "drop",
            "stolen", "migrated", "peer_out", "eq_depth", "cq_depth",
            "slots_busy")
    head = f"{'window':>14s} " + " ".join(f"{c[:9]:>9s}" for c in cols)
    lines = [head, "-" * len(head)]
    for w0 in range(0, n, win):
        w1 = min(w0 + win, n)
        t0, t1 = w0 * dt / 1e3, w1 * dt / 1e3
        row = [f"{t0:6.1f}-{t1:5.1f}s"]
        for c in cols:
            seg = ts[c][w0:w1]
            # gauges read better as window means, events as window sums
            v = seg.mean() if c in ("eq_depth", "cq_depth",
                                    "slots_busy") else seg.sum()
            row.append(f"{v:9.1f}" if isinstance(v, float) and c in (
                "eq_depth", "cq_depth", "slots_busy") else f"{int(v):9d}")
        lines.append(" ".join(row))
    return "\n".join(lines)


def tail_table(tm: dict) -> str:
    lines = [
        f"settled: {tm['hit']} hit / {tm['miss']} miss / "
        f"{tm['drop']} drop   hit-rate {100 * tm['hit_rate']:.1f}%",
        f"drops by cause: infeasible={tm['drops_by_cause']['infeasible']} "
        f"unstolen={tm['drops_by_cause']['unstolen']} "
        f"queue_full={tm['drops_by_cause']['queue_full']}",
        f"QoS utility {tm['qos_utility']:.0f}   "
        f"QoE utility {tm['qoe_utility']:.0f}",
        f"{'':16s} {'p50':>8s} {'p95':>8s} {'p99':>8s}   (ms)",
        "deadline slack  " + " ".join(
            f"{tm['slack_ms'][p]:8.0f}" for p in ("p50", "p95", "p99")),
        "completion lat  " + " ".join(
            f"{tm['latency_ms'][p]:8.0f}" for p in ("p50", "p95", "p99")),
        f"deadline-hit tail (per ~1s window): "
        f"mean {100 * tm['deadline_hit']['mean']:.1f}%  "
        f"p95 {100 * tm['deadline_hit']['p95']:.1f}%  "
        f"p99 {100 * tm['deadline_hit']['p99']:.1f}%",
        "QoE frequency (per task type): " + "  ".join(
            f"{k}={100 * v:.1f}%" for k, v in tm["qoe_frequency"].items()),
    ]
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="rush-hour", choices=names())
    ap.add_argument("--policy", default="DEMS-A")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--duration-ms", type=float, default=None)
    ap.add_argument("--dt", type=float, default=25.0)
    ap.add_argument("--hist-bins", type=int, default=64)
    ap.add_argument("--hist-max-ms", type=float, default=4000.0)
    ap.add_argument("--windows", type=int, default=12,
                    help="timeline rows (ticks aggregate into windows)")
    ap.add_argument("--json", help="write full metrics document here")
    ap.add_argument("--csv", help="write per-tick series CSV here")
    ap.add_argument("--perfetto", help="write Chrome/Perfetto trace here")
    args = ap.parse_args()

    overrides = dict(seed=args.seed)
    if args.duration_ms is not None:
        overrides["duration_ms"] = args.duration_ms
    spec = get(args.scenario, **overrides)
    tspec = TraceSpec.full(hist_bins=args.hist_bins,
                           hist_max_ms=args.hist_max_ms)
    res = run_scenario_fleet(spec, args.policy, dt=args.dt, trace=tspec)
    metrics.check_conservation(res.counters)

    ts = metrics.time_series(res.counters)
    tm = metrics.tail_metrics(res.counters, tspec, list(spec.model_names))
    n_edges = np.asarray(res.counters.valid).shape[1]
    print(f"{spec.name} × {args.policy} seed={args.seed} "
          f"({spec.duration_ms / 1e3:.0f}s, dt={args.dt:.0f}ms, "
          f"{len(ts['arrivals'])} ticks, {n_edges} edges)\n")
    print(timeline(ts, args.dt, width=args.windows))
    print()
    print(tail_table(tm))
    print("\ntask conservation: arrived = settled + in-flight "
          "(residual 0 on every tick) ✓")

    if args.json:
        with open(args.json, "w") as f:
            f.write(metrics.to_json(res.counters, tspec,
                                    list(spec.model_names), indent=2))
        print(f"wrote {args.json}")
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(metrics.to_csv(res.counters))
        print(f"wrote {args.csv}")
    if args.perfetto:
        with open(args.perfetto, "w") as f:
            f.write(metrics.to_perfetto(res.counters, dt_ms=args.dt))
        print(f"wrote {args.perfetto}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
