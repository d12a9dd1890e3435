"""Paper Fig. 13 weak scaling + beyond-paper SPMD fleet scaling.

(a) Paper-style: replicate independent edge simulators 7 → 28 edges (the
    paper's 1→4 host machines); per-edge utility/completion should stay
    flat.
(b) Beyond paper: the JAX fleet simulator steps 256 edges as ONE SPMD
    program (vmap + NamedSharding over the fleet axis) — city-scale
    emulation the Java platform cannot reach.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import QOS, Rows, timed
from repro.core.schedulers import make_policy
from repro.core.task import PASSIVE, TABLE1
from repro.sim.engine import run_policy
from repro.sim.fleet_jax import simulate_fleet
from repro.sim.workloads import standard


def main(quick: bool = False, rows: Rows | None = None) -> dict:
    rows = rows or Rows()
    duration = 120_000.0 if quick else 300_000.0
    out = {}

    # (a) replicated discrete-event edges (3D-P per edge, like the paper)
    for n_edges in ((7,) if quick else (7, 14, 28)):
        results = []
        for e in range(n_edges):
            arrivals = standard("3D-P", duration_ms=duration, seed=100 + e)
            r, us = timed(lambda: run_policy(
                make_policy("DEMS"), arrivals, duration, seed=e, **QOS))
            results.append(r)
        comp = np.mean([r.completion_rate for r in results])
        util = np.mean([r.qos_utility for r in results])
        out[n_edges] = (comp, util)
        rows.add(f"fig13/event_sim/{n_edges}edges", us,
                 f"completed={100 * comp:.1f}% qos/edge={util:.0f} "
                 f"(paper: ~83% flat)")

    # (b) one SPMD program over the fleet
    models = [TABLE1[n] for n in PASSIVE]
    n_fleet = 32 if quick else 256
    final, us = timed(lambda: simulate_fleet(
        models, "DEMS", n_edges=n_fleet, drones_per_edge=3,
        duration_ms=min(duration, 120_000.0)))
    succ = np.asarray(final.n_success).sum()
    gen = n_fleet * 3 * int(min(duration, 120_000.0) / 1000) * len(models)
    rows.add(f"fig13/fleet_spmd/{n_fleet}edges", us,
             f"completed={100 * succ / gen:.1f}% "
             f"({succ:.0f}/{gen} tasks in one jitted program)")
    out["fleet"] = (n_fleet, succ, gen)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = Rows()
    main(rows=rows)
    rows.emit()
