"""Graceful-degradation scoreboard → ``degradation`` section of
``BENCH_fleet.json``.

    PYTHONPATH=src python benchmarks/bench_degradation.py            # full
    PYTHONPATH=src python benchmarks/bench_degradation.py --quick    # CI

For each hostile registry scenario (``flash-crowd``, ``ddos-flood``,
``partition``, ``brownout``) and each policy, runs the fleet simulator
twice — once with the scenario's fault schedule, once with its
fault-free twin (``faults=None``, same drones/bursts/seed) — and
reports **retention**: the fraction of fault-free QoS utility, QoE
utility and completion rate the policy still earns under the injected
faults.  Retention is the paper-facing robustness number: a policy that
degrades gracefully keeps most of its utility through a crash or
brownout instead of collapsing.

Every hostile run is executed with the flight recorder on and the
conservation ledger (``arrived = settled + in-flight``) is asserted
exactly — a leaking ledger fails the benchmark regardless of scores.

``--check`` re-validates the scoreboard invariants (every retention is
a finite number, every ledger balanced) and exits non-zero on
violation; ``--out`` merges the section into the committed baseline
next to ``throughput``/``controller``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_fleet.json"

HOSTILE = ("flash-crowd", "ddos-flood", "partition", "brownout")


def _ratio(num: float, den: float) -> float | None:
    """Retention num/den; None when the baseline earned nothing."""
    if den == 0.0:
        return None
    return round(num / den, 4)


def run_degradation(*, scenarios=HOSTILE,
                    policies=("DEMS-A", "GEMS-COOP"),
                    duration_ms: float = 120_000.0,
                    dt: float = 25.0) -> dict:
    """Per-(scenario, policy) retention vs the fault-free twin."""
    from repro.obs.metrics import check_conservation
    from repro.obs.trace import TraceSpec
    from repro.scenarios.registry import get
    from repro.scenarios.runner import fleet_summary, run_scenario_fleet

    trace = TraceSpec(counters=True)
    out: dict = {}
    for name in scenarios:
        spec = get(name, duration_ms=duration_ms)
        if spec.faults is None:
            raise ValueError(f"scenario {name!r} has no fault schedule")
        calm = dataclasses.replace(spec, faults=None)
        out[name] = {}
        for policy in policies:
            res = run_scenario_fleet(spec, policy, dt=dt, trace=trace)
            check_conservation(res.counters)
            hostile = fleet_summary(res.final)
            base = fleet_summary(run_scenario_fleet(calm, policy, dt=dt))
            out[name][policy] = dict(
                qos=round(hostile["qos_utility"], 1),
                qoe=round(hostile["qoe_utility"], 1),
                completion_rate=round(hostile["completion_rate"], 4),
                dropped=hostile["dropped"],
                qos_retention=_ratio(hostile["qos_utility"],
                                     base["qos_utility"]),
                qoe_retention=_ratio(hostile["qoe_utility"],
                                     base["qoe_utility"]),
                completion_retention=_ratio(hostile["completion_rate"],
                                            base["completion_rate"]),
                conservation="exact")
    return dict(duration_ms=duration_ms, scenarios=out)


def check_section(section: dict) -> list[str]:
    """Scoreboard invariants; returns human-readable violations."""
    bad = []
    for name, by_policy in section["scenarios"].items():
        for policy, row in by_policy.items():
            if row.get("conservation") != "exact":
                bad.append(f"{name}/{policy}: ledger not exact")
            for key in ("qos_retention", "completion_retention"):
                v = row.get(key)
                if v is None or not (v == v and abs(v) < 1e6):
                    bad.append(f"{name}/{policy}: {key} is {v!r}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="45 s missions, 2 policies (CI smoke)")
    ap.add_argument("--scenarios", nargs="*", default=None,
                    help=f"hostile scenarios to score (default {HOSTILE})")
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="BENCH json to merge the degradation section into")
    ap.add_argument("--no-write", action="store_true",
                    help="print the section, leave the json untouched")
    ap.add_argument("--check", action="store_true",
                    help="gate: fail on non-finite retention or a "
                         "leaking conservation ledger")
    args = ap.parse_args(argv)

    kw = dict(duration_ms=45_000.0, policies=("DEMS-A", "GEMS-COOP")) \
        if args.quick else dict(
            duration_ms=120_000.0,
            policies=("DEMS-A", "GEMS-COOP", "SJF-E+C", "GEMS-B"))
    if args.scenarios:
        kw["scenarios"] = tuple(args.scenarios)
    section = run_degradation(**kw)
    mode = "quick" if args.quick else "full"
    print(json.dumps({mode: {"degradation": section}}, indent=2))

    if args.check:
        bad = check_section(section)
        for b in bad:
            print(f"FAIL: {b}")
        if bad:
            return 1
        print("degradation scoreboard invariants hold")

    if not args.no_write:
        path = pathlib.Path(args.out)
        data = json.load(open(path)) if path.exists() else {}
        data.setdefault(mode, {})["degradation"] = section
        path.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")
        print(f"wrote {mode}.degradation -> {path}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
