"""Paper Fig. 11/12 + App. C (Fig. 21): DEMS-A under network variability.

Latency shaping: the §8.5 trapezium waveform (0→400 ms).  Bandwidth
shaping: synthetic cellular traces (Fig. 2c analogue).  Expectation:
DEMS-A ≥ DEMS on QoS utility with similar on-time tasks (paper: +16–27 %).

``main_fleet`` repeats the latency-shaped comparison on the JAX fleet
simulator and adds the congestion regimes (``cloud-crunch``: a saturated
finite FaaS pool; ``bw-fade``: a cellular deep fade): the seed sweep for
each policy runs as one compiled program (`run_fleet_batch`), checking
that the vmapped DEMS-A adaptation shows the same qualitative gain as
the event-driven oracle now that the fleet cloud is contended and
bandwidth-shaped rather than elastic.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.common import QOS, Rows, timed
from repro.core.schedulers import make_policy
from repro.sim.engine import run_policy
from repro.sim.network import (CloudLatencyModel, cellular_bandwidth_trace,
                               trapezium)
from repro.sim.workloads import standard


def main(quick: bool = False, rows: Rows | None = None) -> dict:
    rows = rows or Rows()
    workloads = ("4D-P",) if quick else ("4D-P", "3D-P")
    seeds = (7,) if quick else (7, 17, 27)
    duration = 300_000.0
    out = {}
    for wl in workloads:
        arrivals = standard(wl, duration_ms=duration, seed=1)
        for variability in ("latency", "bandwidth"):
            if variability == "latency":
                cm = CloudLatencyModel(latency_at=trapezium())
            else:
                cm = CloudLatencyModel(
                    bandwidth_at=cellular_bandwidth_trace(seed=3))
            gains, comps = [], []
            for seed in seeds:
                kw = dict(QOS, cloud_model=cm)
                base, _ = timed(lambda: run_policy(
                    make_policy("DEMS"), arrivals, duration, seed=seed,
                    **kw))
                adpt, us = timed(lambda: run_policy(
                    make_policy("DEMS-A"), arrivals, duration, seed=seed,
                    **kw))
                gains.append(100 * (adpt.qos_utility / base.qos_utility - 1))
                comps.append(adpt.completed / max(base.completed, 1))
                out[(wl, variability, seed)] = (base, adpt)
            rows.add(f"fig11/{wl}/{variability}", us,
                     f"DEMS-A qos {np.median(gains):+.1f}% "
                     f"(all {[f'{g:+.0f}' for g in gains]}), tasks "
                     f"x{np.median(comps):.2f} (paper: +15..27% qos)")
    return out


def main_fleet(quick: bool = False, rows: Rows | None = None) -> dict:
    """Fleet-side Fig. 11: DEMS-A vs DEMS under the §8.5 trapezium *and*
    under the congestion scenarios (finite cloud pool, bandwidth fade),
    every per-policy seed sweep batched into a single jit."""
    from repro.scenarios import (ScenarioSpec, ThetaTrapezium,
                                 fleet_summary_batch, get,
                                 run_scenario_fleet_batch)

    rows = rows or Rows()
    spec = ScenarioSpec(name="fig11-fleet", theta=ThetaTrapezium(),
                        duration_ms=120_000.0 if quick else 300_000.0)
    if quick:   # compress the 300 s trapezium into the shorter mission
        spec = dataclasses.replace(spec, theta=ThetaTrapezium(
            ramp_up=(24_000.0, 36_000.0), ramp_down=(84_000.0, 96_000.0)))
    seeds = (7,) if quick else (7, 17, 27)
    duration = 60_000.0 if quick else 300_000.0
    out = {}
    runs = [("latency", spec),
            ("cloud-crunch", get("cloud-crunch", duration_ms=duration)),
            ("bw-fade", get("bw-fade", duration_ms=duration))]
    for label, sc in runs:
        base, _ = timed(lambda: fleet_summary_batch(
            run_scenario_fleet_batch(sc, "DEMS", seeds)))
        adpt, us = timed(lambda: fleet_summary_batch(
            run_scenario_fleet_batch(sc, "DEMS-A", seeds)))
        gains = [100 * (a["qos_utility"] / b["qos_utility"] - 1)
                 for a, b in zip(adpt, base)]
        out[label] = (base, adpt)
        rows.add(f"fig11/fleet/{label}", us,
                 f"DEMS-A qos {np.median(gains):+.1f}% over {len(seeds)} "
                 f"seeds (one-jit batch; paper oracle: +15..27%)")

    # Fig. 12: adaptation dynamics — the per-tick t̂ trace carried out of
    # the scan (FleetResult.t_hat) shows the estimator inflating with the
    # trapezium and cooling back down once θ drops
    out["trace"], us = timed(
        lambda: adaptation_trace(spec, "DEMS-A", seeds[0]))
    rows.add("fig12/fleet/t_hat", us,
             f"t̂ inflation: peak +{out['trace']['peak_ms']:.0f} ms, "
             f"{100 * out['trace']['inflated_frac']:.0f}% of mission "
             f"above static (per-tick trace)")
    return out


def adaptation_trace(spec, policy: str, seed: int = 7) -> dict:
    """Fig. 12-style adaptation dynamics from the fleet t̂ telemetry.

    Runs one scenario with ``trace=TraceSpec(t_hat=True)`` (the
    flight recorder) and reduces the per-tick
    ``t_hat`` trace ``[T, E, M]`` (DEMS-A's adapted cloud-latency
    estimate) to inflation statistics against the static Table-1 t̂.
    """
    import dataclasses as dc

    from repro.obs import TraceSpec
    from repro.scenarios import run_scenario_fleet

    res = run_scenario_fleet(dc.replace(spec, seed=seed), policy,
                             trace=TraceSpec(t_hat=True))
    t_hat = np.asarray(res.t_hat)                      # [T, E, M]
    static = np.asarray([m.t_cloud for m in spec.models])
    excess = t_hat - static[None, None, :]
    return dict(peak_ms=float(excess.max()),
                inflated_frac=float((excess.max(axis=(1, 2)) > 1.0).mean()),
                t_hat=t_hat)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = Rows()
    main(rows=rows)
    main_fleet(rows=rows)
    rows.emit()
