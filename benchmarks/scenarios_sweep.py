"""Sweep scenario × policy × seed and report completion rate / QoS / QoE.

    PYTHONPATH=src python benchmarks/scenarios_sweep.py \
        --backend oracle --duration-ms 120000
    PYTHONPATH=src python benchmarks/scenarios_sweep.py \
        --backend fleet --policies DEMS DEMS-A GEMS-COOP --seeds 0 1 2
    PYTHONPATH=src python benchmarks/scenarios_sweep.py --quick

Oracle rows carry the full event-driven metric set (windows, stealing,
migration); fleet rows add the cross-edge peer-offload count.  The fleet
backend runs the **whole sweep as one compiled program**: scenarios are
padded to a common shape and policies are runtime parameters
(`run_registry_sweep`), so scenarios × policies × seeds cost a single
jit, not one per (scenario, policy).  Output is CSV on stdout, one row
per (scenario, policy, seed).  ``--quick`` is the CI smoke path: one
calm and one congested short scenario on both backends.
"""
from __future__ import annotations

import argparse

from repro.scenarios import get, names, run_registry_sweep, \
    run_scenario_oracle

ORACLE_POLICIES = ("EDF-E+C", "DEMS", "GEMS")
FLEET_POLICIES = ("EDF", "HPF", "CLD", "EDF-E+C", "SJF-E+C", "SOTA1",
                  "SOTA2", "DEMS", "DEMS-A", "DEMS-COOP", "GEMS",
                  "GEMS-A", "GEMS-COOP", "GEMS-B")


def sweep_oracle(scenarios, policies, duration_ms) -> None:
    print("scenario,policy,generated,completed,completion_rate,"
          "qos_utility,qoe_utility,stolen,migrated,gems_rescheduled")
    for sc in scenarios:
        spec = get(sc, duration_ms=duration_ms) if duration_ms else get(sc)
        for pol in policies:
            r = run_scenario_oracle(spec, pol).merged
            print(f"{sc},{pol},{r.generated},{r.completed},"
                  f"{r.completion_rate:.4f},{r.qos_utility:.0f},"
                  f"{r.qoe_utility:.0f},{r.stolen},{r.migrated},"
                  f"{r.gems_rescheduled}")


def sweep_fleet(scenarios, policies, duration_ms, dt, seeds) -> None:
    print("scenario,policy,seed,completed,completion_rate,qos_utility,"
          "qoe_utility,stolen,peer_offloaded")
    rows = run_registry_sweep(tuple(scenarios), tuple(policies),
                              tuple(seeds), dt=dt, duration_ms=duration_ms)
    for s in rows:
        print(f"{s['scenario']},{s['policy']},{s['seed']},{s['completed']},"
              f"{s['completion_rate']:.4f},{s['qos_utility']:.0f},"
              f"{s['qoe_utility']:.0f},{s['stolen']},"
              f"{s['peer_offloaded']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="oracle",
                    choices=("oracle", "fleet"))
    ap.add_argument("--scenarios", nargs="*", default=list(names()))
    ap.add_argument("--policies", nargs="*", default=None)
    ap.add_argument("--seeds", nargs="*", type=int, default=[0],
                    help="fleet backend: batched one-jit seed sweep")
    ap.add_argument("--duration-ms", type=float, default=None)
    ap.add_argument("--dt", type=float, default=25.0)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: one short scenario, both backends")
    args = ap.parse_args()

    if args.quick:
        # one calm and one congested scenario so neither the elastic-limit
        # nor the finite-pool/bw-shaping path can rot; SOTA2 + GEMS-B keep
        # the newly-covered routing/winnability branches in the smoke
        sweep_oracle(("baseline", "cloud-crunch"), ("DEMS",), 20_000.0)
        sweep_fleet(("baseline", "cloud-crunch"),
                    ("DEMS", "DEMS-A", "SOTA2", "GEMS-B"),
                    20_000.0, args.dt, (0, 1))
        return
    if args.backend == "oracle":
        sweep_oracle(args.scenarios, args.policies or ORACLE_POLICIES,
                     args.duration_ms)
    else:
        sweep_fleet(args.scenarios, args.policies or FLEET_POLICIES,
                    args.duration_ms, args.dt, args.seeds)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
