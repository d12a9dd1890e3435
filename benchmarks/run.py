"""Benchmark harness entry point: one module per paper table/figure.

``python -m benchmarks.run``          — full runs (≈ paper durations)
``python -m benchmarks.run --quick``  — reduced sweep for CI

Prints ``name,us_per_call,derived`` CSV.  A figure that raises is
reported (an ``<name>/ERROR`` row and a line on stderr), the remaining
figures still run, and the run exits 1.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.common import Rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run")
    args = ap.parse_args()

    from benchmarks import (fig8_baselines, fig10_incremental,
                            fig11_variability, fig13_scaling, fig14_gems,
                            table1_profiles, roofline_report)
    modules = {
        "table1": table1_profiles,
        "fig8": fig8_baselines,
        "fig10": fig10_incremental,
        "fig11": fig11_variability,
        "fig13": fig13_scaling,
        "fig14": fig14_gems,
        "roofline": roofline_report,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = Rows()
    failed = []
    t0 = time.time()
    for name, mod in modules.items():
        t = time.time()
        try:
            mod.main(quick=args.quick, rows=rows)
            rows.add(f"{name}/elapsed_s", (time.time() - t) * 1e6,
                     f"{time.time() - t:.1f}s")
        except Exception as e:  # noqa: BLE001 - report, run the rest
            failed.append(name)
            rows.add(f"{name}/ERROR", 0.0, f"{type(e).__name__}: {e}")
            traceback.print_exc()
            print(f"[benchmark {name} failed: {e}]", file=sys.stderr)
    rows.add("total/elapsed_s", (time.time() - t0) * 1e6,
             f"{time.time() - t0:.1f}s")
    rows.emit()
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
