"""Paper Fig. 8/9: DEMS vs the seven baselines across the six workloads.

Reports per (policy × workload): tasks completed %, QoS utility, and the
paper's headline ratios (DEMS completion range, utility multiple vs the
weakest baseline).

``--backend fleet`` repeats the whole comparison on the JAX fleet
simulator: every baseline is a runtime ``PolicyParams`` branch of the
same compiled tick program, so the full workload × policy grid runs as
**one** ``run_batch`` program instead of one event-driven simulation per
cell — the coverage-matrix close-out that lets fleet-scale sweeps
reproduce the paper's baseline claims without the oracle.
"""
from __future__ import annotations

import argparse

from benchmarks.common import QOS, Rows, timed
from repro.core.schedulers import BASELINES, make_policy
from repro.sim.engine import run_policy
from repro.sim.workloads import STANDARD_WORKLOADS, standard

POLICIES = BASELINES + ("DEMS",)


def main(quick: bool = False, rows: Rows | None = None) -> dict:
    rows = rows or Rows()
    workloads = ("2D-P", "3D-A") if quick else STANDARD_WORKLOADS
    duration = 120_000.0 if quick else 300_000.0
    out: dict[tuple[str, str], object] = {}
    for wl in workloads:
        arrivals = standard(wl, duration_ms=duration, seed=1)
        for pol in POLICIES:
            r, us = timed(lambda: run_policy(
                make_policy(pol), arrivals, duration, seed=7, **QOS))
            out[(wl, pol)] = r
            rows.add(f"fig8/{wl}/{pol}", us,
                     f"completed={100 * r.completion_rate:.1f}% "
                     f"qos={r.qos_utility:.0f}")
    # headline claims
    dems = [out[(wl, "DEMS")] for wl in workloads]
    comp = [r.completion_rate for r in dems]
    ratios = []
    for wl in workloads:
        base_best = max(out[(wl, p)].qos_utility for p in BASELINES)
        base_worst = min(out[(wl, p)].qos_utility for p in BASELINES)
        ratios.append(out[(wl, "DEMS")].qos_utility / max(base_worst, 1))
        rows.add(f"fig8/{wl}/DEMS_vs_best_baseline", 0.0,
                 f"x{out[(wl, 'DEMS')].qos_utility / max(base_best, 1):.2f}")
    rows.add("fig8/DEMS_completion_range", 0.0,
             f"{100 * min(comp):.0f}%..{100 * max(comp):.0f}% "
             f"(paper: 77..88%)")
    rows.add("fig8/DEMS_utility_vs_worst_baseline", 0.0,
             f"up to x{max(ratios):.1f} (paper: up to x2.7)")
    return out


def main_fleet(quick: bool = False, rows: Rows | None = None) -> dict:
    """Fig. 8 on the fleet backend: workloads × (baselines + DEMS) as one
    compiled program (policy flags are runtime, shapes padded per
    workload by ``build_fleet_batch``)."""
    import jax

    from repro.core.task import ACTIVE, PASSIVE
    from repro.scenarios import (DroneSpec, ScenarioSpec, compile_fleet,
                                 fleet_summary)
    from repro.sim.fleet_jax import build_fleet_batch, run_batch

    rows = rows or Rows()
    workloads = ("2D-P", "3D-A") if quick else STANDARD_WORKLOADS
    duration = 120_000.0 if quick else 300_000.0
    runs, tags = [], []
    for wl in workloads:
        names = PASSIVE if wl.endswith("P") else ACTIVE
        spec = ScenarioSpec(
            name=wl, model_names=names, duration_ms=duration, seed=1,
            drones=tuple(DroneSpec() for _ in range(int(wl[0]))),
            cloud_concurrency=QOS["cloud_concurrency"])
        sig = compile_fleet(spec)
        for pol in POLICIES:
            runs.append((spec.models, pol, sig, spec.cloud_concurrency))
            tags.append((wl, pol))
    batch = build_fleet_batch(runs)
    final, us = timed(lambda: jax.device_get(run_batch(batch)))
    out: dict[tuple[str, str], dict] = {}
    for i, (wl, pol) in enumerate(tags):
        s = fleet_summary(jax.tree.map(lambda a, i=i: a[i], final))
        out[(wl, pol)] = s
        rows.add(f"fig8/fleet/{wl}/{pol}", us / len(tags),
                 f"completed={100 * s['completion_rate']:.1f}% "
                 f"qos={s['qos_utility']:.0f}")
    comp, ratios = [], []
    for wl in workloads:
        dems = out[(wl, "DEMS")]
        comp.append(dems["completion_rate"])
        base_best = max(out[(wl, p)]["qos_utility"] for p in BASELINES)
        base_worst = min(out[(wl, p)]["qos_utility"] for p in BASELINES)
        ratios.append(dems["qos_utility"] / max(base_worst, 1))
        rows.add(f"fig8/fleet/{wl}/DEMS_vs_best_baseline", 0.0,
                 f"x{dems['qos_utility'] / max(base_best, 1):.2f}")
    rows.add("fig8/fleet/DEMS_completion_range", 0.0,
             f"{100 * min(comp):.0f}%..{100 * max(comp):.0f}% "
             f"(one-program batch; paper oracle: 77..88%)")
    rows.add("fig8/fleet/DEMS_utility_vs_worst_baseline", 0.0,
             f"up to x{max(ratios):.1f} (paper: up to x2.7)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="oracle",
                    choices=("oracle", "fleet", "both"))
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    rows = Rows()
    if args.backend in ("oracle", "both"):
        main(quick=args.quick, rows=rows)
    if args.backend in ("fleet", "both"):
        main_fleet(quick=args.quick, rows=rows)
    rows.emit()
