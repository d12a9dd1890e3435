"""Paper Fig. 10: incremental benefit of migration (DEM) and stealing
(DEMS) over the E+C baseline."""
from __future__ import annotations

from benchmarks.common import QOS, Rows, timed
from repro.core.schedulers import make_policy
from repro.sim.engine import run_policy
from repro.sim.workloads import STANDARD_WORKLOADS, standard


def main(quick: bool = False, rows: Rows | None = None) -> dict:
    rows = rows or Rows()
    workloads = ("4D-P", "4D-A") if quick else STANDARD_WORKLOADS
    duration = 120_000.0 if quick else 300_000.0
    out = {}
    for wl in workloads:
        arrivals = standard(wl, duration_ms=duration, seed=1)
        for pol in ("EDF-E+C", "DEM", "DEMS"):
            r, us = timed(lambda: run_policy(
                make_policy(pol), arrivals, duration, seed=7, **QOS))
            out[(wl, pol)] = r
            rows.add(f"fig10/{wl}/{pol}", us,
                     f"tasks={r.completed} qos={r.qos_utility:.0f} "
                     f"migrated={r.migrated} stolen={r.stolen} "
                     f"edge_util={100 * r.edge_utilization:.0f}%")
        e, d, s = (out[(wl, p)] for p in ("EDF-E+C", "DEM", "DEMS"))
        rows.add(f"fig10/{wl}/delta", 0.0,
                 f"DEM qos {100 * (d.qos_utility / e.qos_utility - 1):+.1f}% "
                 f"DEMS tasks {100 * (s.completed / e.completed - 1):+.1f}% "
                 f"qos {100 * (s.qos_utility / e.qos_utility - 1):+.1f}% "
                 f"(paper 4D-A: +10% tasks, +5% qos)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = Rows()
    main(rows=rows)
    rows.emit()
