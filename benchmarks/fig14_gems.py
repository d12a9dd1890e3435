"""Paper Fig. 14/15: GEMS vs DEMS on the QoE workloads WL1/WL2.

Two regimes (see benchmarks/common.py): the faithful §8.7 sleep-semantics
elastic-cloud setup, and a constrained-cloud/bursty-edge stress regime
where queue-wait failures dominate and GEMS's preemptive rescheduling has
the most headroom.  Medians over 5 seeds.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import GEMS_SLEEP, GEMS_STRESS, Rows, timed
from repro.core.schedulers import make_policy
from repro.sim.engine import run_policy
from repro.sim.workloads import gems_workload


def main(quick: bool = False, rows: Rows | None = None) -> dict:
    rows = rows or Rows()
    seeds = (101,) if quick else (101, 102, 103, 104, 105)
    duration = 300_000.0
    out = {}
    regimes = {"sleep": (GEMS_SLEEP, 5), "stress": (GEMS_STRESS, 3)}
    for regime, (kw, drones) in regimes.items():
        for wl in ("WL1", "WL2"):
            for alpha in (0.9, 1.0):
                arrivals = gems_workload(wl, alpha, n_drones=drones, seed=2)
                dq, dt, rs, qoe_abs, qoe_b = [], [], [], [], []
                for seed in seeds:
                    d, _ = timed(lambda: run_policy(
                        make_policy("DEMS"), arrivals, duration, seed=seed,
                        **kw))
                    g, us = timed(lambda: run_policy(
                        make_policy("GEMS"), arrivals, duration, seed=seed,
                        **kw))
                    gb, _ = timed(lambda: run_policy(
                        make_policy("GEMS-B"), arrivals, duration,
                        seed=seed, **kw))
                    dq.append(100 * (g.qoe_utility /
                                     max(d.qoe_utility, 1) - 1))
                    dt.append(100 * (g.total_utility / d.total_utility - 1))
                    rs.append(g.gems_rescheduled)
                    qoe_abs.append((d.qoe_utility, g.qoe_utility))
                    qoe_b.append(gb.qoe_utility)
                    out[(regime, wl, alpha, seed)] = (d, g, gb)
                rows.add(f"fig14/{regime}/{wl}/a{alpha}", us,
                         f"dQoE med {np.median(dq):+.0f}% "
                         f"dTotal {np.median(dt):+.1f}% "
                         f"resched~{int(np.median(rs))} "
                         f"QoE {np.median([a for a, _ in qoe_abs]):.0f}"
                         f"->{np.median([b for _, b in qoe_abs]):.0f} "
                         f"(GEMS-B {np.median(qoe_b):.0f})")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = Rows()
    main(rows=rows)
    rows.emit()
