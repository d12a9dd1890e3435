"""CI chaos smoke: one hostile scenario end-to-end, streamed.

    PYTHONPATH=src python benchmarks/chaos_smoke.py --out scoreboard.json

Builds a hostile mission — the baseline workload with an edge scheduler
crash *and* a correlated cloud brownout injected — and drives it through
the streaming control plane, asserting the three chaos-engine
guarantees end-to-end:

1. **streaming equivalence under faults** — a
   :class:`repro.serve.controller.FleetController` fed the compiled
   fault lanes window-by-window finishes in the bitwise-identical
   ``EdgeState`` as one replay call (crashes and brownouts do not break
   the scan-composition contract);
2. **exact conservation** — the flight-recorder ledger
   ``arrived = settled + in-flight`` balances on every tick through the
   crash window (flushed tasks are *settled as drops*, never leaked);
3. **degradation scoreboard** — the quick retention scoreboard for two
   hostile registry scenarios is computed and written to ``--out`` as
   the uploadable CI artifact.

Exit code is non-zero on any violation.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chaos_scoreboard.json",
                    help="degradation scoreboard artifact path")
    ap.add_argument("--duration", type=float, default=45_000.0)
    args = ap.parse_args(argv)

    from bench_degradation import check_section, run_degradation
    from repro.faults import Brownout, EdgeCrash, FaultSpec
    from repro.obs.metrics import check_conservation, tail_metrics
    from repro.obs.trace import TraceSpec
    from repro.scenarios.registry import get
    from repro.scenarios.runner import (assert_streaming_equivalence,
                                        run_scenario_fleet)

    d = args.duration
    spec = dataclasses.replace(
        get("baseline", duration_ms=d),
        name="chaos-smoke",
        faults=FaultSpec(
            crashes=(EdgeCrash(edge=0, start_ms=0.2 * d, end_ms=0.5 * d),),
            brownouts=(Brownout(start_ms=0.1 * d, end_ms=0.9 * d,
                                theta_ms=300.0, ramp_ms=0.2 * d),)))

    print("1/3 streaming equivalence under edge crash + brownout …")
    summary = assert_streaming_equivalence(spec, "DEMS-A")
    print(f"    bitwise OK: {summary}")

    print("2/3 conservation ledger through the crash window …")
    trace = TraceSpec(counters=True)
    res = run_scenario_fleet(spec, "DEMS-A", trace=trace)
    check_conservation(res.counters)
    tail = tail_metrics(res.counters, trace)
    print(f"    exact; drops by cause: {tail['drops_by_cause']}")
    if tail["drops_by_cause"]["crash"] == 0:
        print("FAIL: crash window injected but no crash-flush drops "
              "recorded — fault lanes not reaching the tick program")
        return 1

    print("3/3 degradation scoreboard (quick) …")
    section = run_degradation(scenarios=("ddos-flood", "brownout"),
                              policies=("DEMS-A", "GEMS-COOP"),
                              duration_ms=d)
    bad = check_section(section)
    for b in bad:
        print(f"FAIL: {b}")
    if bad:
        return 1
    with open(args.out, "w") as f:
        json.dump(dict(quick=dict(degradation=section)), f, indent=2)
    print(f"    wrote scoreboard -> {args.out}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
