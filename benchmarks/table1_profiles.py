"""Paper Table 1 / App. A-B: profile identities and sampler calibration.

Verifies γ columns and that the latency samplers reproduce the p95/p99
estimation methodology (edge actuals ≤ p99 estimate ~99 % of the time;
cloud actuals ≤ p95 estimate ~95 %).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Rows
from repro.core.task import ACTIVE, TABLE1
from repro.sim.network import CloudLatencyModel, EdgeLatencyModel


def main(quick: bool = False, rows: Rows | None = None) -> dict:
    rows = rows or Rows()
    rng = np.random.default_rng(0)
    em, cm = EdgeLatencyModel(), CloudLatencyModel(cold_start_p=0.0)
    n = 1000 if quick else 5000
    out = {}
    for name in ACTIVE:
        m = TABLE1[name]
        es = np.array([em.sample(rng, m.t_edge) for _ in range(n)])
        cs = np.array([cm.sample(rng, m.t_cloud, 0.0) for _ in range(n)])
        p_edge = float(np.mean(es <= m.t_edge))
        p_cloud = float(np.mean(cs <= m.t_cloud))
        out[name] = (p_edge, p_cloud)
        rows.add(f"table1/{name}", 0.0,
                 f"gammaE={m.gamma_edge} gammaC={m.gamma_cloud} "
                 f"P(edge<=t)={p_edge:.3f} P(cloud<=t_hat)={p_cloud:.3f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = Rows()
    main(rows=rows)
    rows.emit()
