"""Run a named scenario through the oracle and/or the JAX fleet simulator.

    PYTHONPATH=src python examples/run_scenario.py --scenario rush-hour \
        --policy DEMS --backend both
    PYTHONPATH=src python examples/run_scenario.py --scenario flaky-cloud \
        --policy DEMS-A --backend fleet --seeds 0 1 2
    PYTHONPATH=src python examples/run_scenario.py --scenario hetero-edges \
        --policy DEMS --backend fleet --cooperation

``--cooperation`` enables the cross-edge peer-offload exchange on the
fleet backend; a ``*-COOP`` policy name enables it on both backends
(the oracle runs the lockstep multi-edge ``FleetOracle``).  Passing more than one
``--seeds`` value runs the fleet backend's whole seed sweep as a single
compiled program (``run_fleet_batch``).  ``--trace`` turns on the
flight recorder (fleet backend, single run) and prints the tail
scoreboard — p50/p95/p99 deadline slack and completion latency,
per-task-type QoE success frequencies, drops by cause — plus the task
conservation residual (always 0).
"""
from __future__ import annotations

import argparse

from repro.core.schedulers import ALL_POLICIES
from repro.scenarios import (fleet_summary, fleet_summary_batch, get, names,
                             run_scenario_fleet, run_scenario_fleet_batch,
                             run_scenario_oracle)
from repro.sim.fleet_jax import FleetPolicy


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="baseline", choices=names())
    ap.add_argument("--policy", default="DEMS")
    ap.add_argument("--backend", default="both",
                    choices=("oracle", "fleet", "both"))
    ap.add_argument("--duration-ms", type=float, default=None,
                    help="override the scenario's mission duration")
    ap.add_argument("--cooperation", action="store_true",
                    help="cross-edge peer offload (fleet backend)")
    ap.add_argument("--seeds", nargs="*", type=int, default=None,
                    help=">1 seed: one-jit batched fleet sweep")
    ap.add_argument("--dt", type=float, default=25.0)
    ap.add_argument("--trace", action="store_true",
                    help="flight recorder: tail metrics + conservation "
                         "ledger (fleet backend)")
    args = ap.parse_args()

    overrides = {}
    if args.duration_ms is not None:
        overrides["duration_ms"] = args.duration_ms
    spec = get(args.scenario, **overrides)
    print(f"scenario={spec.name} edges={spec.n_edges} drones={spec.n_drones}"
          f" models={','.join(spec.model_names)}"
          f" duration={spec.duration_ms / 1000:.0f}s")

    if args.backend in ("oracle", "both"):
        base = args.policy[:-5] if args.policy.endswith("-COOP") \
            else args.policy
        if base not in ALL_POLICIES:
            ap.error(f"--policy {args.policy!r} unknown to the oracle; "
                     f"choose from {ALL_POLICIES} (plus '-COOP' variants)")
        run = run_scenario_oracle(spec, args.policy)
        print("oracle  ", run.merged.summary())
        for e, r in enumerate(run.per_edge):
            print(f"  edge{e} tasks={r.completed}/{r.generated} "
                  f"QoS={r.qos_utility:.0f} util="
                  f"{100 * r.edge_utilization:.0f}%")

    if args.backend in ("fleet", "both"):
        try:
            pol = FleetPolicy.from_name(args.policy)
        except ValueError as e:
            ap.error(str(e))
        if args.cooperation:
            import dataclasses
            pol = dataclasses.replace(pol, cooperation=True)
        if args.seeds and len(args.seeds) > 1:
            final = run_scenario_fleet_batch(spec, pol, tuple(args.seeds),
                                             dt=args.dt)
            for seed, s in zip(args.seeds, fleet_summary_batch(final)):
                print(f"fleet[s{seed}] tasks={s['completed']} "
                      f"({100 * s['completion_rate']:.1f}% of settled) "
                      f"QoS={s['qos_utility']:.0f} "
                      f"QoE={s['qoe_utility']:.0f} stolen={s['stolen']}")
            return
        if args.seeds:
            spec = get(args.scenario, seed=args.seeds[0], **overrides)
        tspec = None
        if args.trace:
            from repro.obs import TraceSpec
            tspec = TraceSpec.full()
        res = run_scenario_fleet(spec, pol, dt=args.dt, trace=tspec)
        final = res.final if tspec else res
        s = fleet_summary(final)
        print(f"fleet    tasks={s['completed']} "
              f"({100 * s['completion_rate']:.1f}% of settled) "
              f"QoS={s['qos_utility']:.0f} QoE={s['qoe_utility']:.0f} "
              f"stolen={s['stolen']} peer_offloaded={s['peer_offloaded']}")
        if tspec:
            import numpy as np

            from repro.obs import metrics
            tm = metrics.tail_metrics(res.counters, tspec,
                                      list(spec.model_names))
            resid = metrics.conservation_ledger(
                res.counters)["residual"]
            print(f"trace    hit_rate={100 * tm['hit_rate']:.1f}% "
                  f"slack p50/p95/p99 = "
                  f"{tm['slack_ms']['p50']:.0f}/"
                  f"{tm['slack_ms']['p95']:.0f}/"
                  f"{tm['slack_ms']['p99']:.0f} ms  latency = "
                  f"{tm['latency_ms']['p50']:.0f}/"
                  f"{tm['latency_ms']['p95']:.0f}/"
                  f"{tm['latency_ms']['p99']:.0f} ms")
            dh = tm["deadline_hit"]
            print(f"         deadline-hit tail (~1s windows): "
                  f"mean={100 * dh['mean']:.1f}% "
                  f"p95={100 * dh['p95']:.1f}% "
                  f"p99={100 * dh['p99']:.1f}%")
            print(f"         QoE freq: " + "  ".join(
                f"{k}={100 * v:.0f}%"
                for k, v in tm['qoe_frequency'].items()))
            print(f"         drops: {tm['drops_by_cause']}  "
                  f"conservation residual max="
                  f"{int(np.abs(resid).max())}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
