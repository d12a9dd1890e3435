"""Aggregate dry-run JSONs into the §Dry-run / §Roofline tables
(markdown printed to stdout; also summarized as CSV rows)."""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import Rows

DRYRUN_DIR = os.environ.get("DRYRUN_DIR", "experiments/dryrun")


def load_all(path: str = DRYRUN_DIR) -> list[dict]:
    out = []
    for f in sorted(glob.glob(os.path.join(path, "*.json"))):
        with open(f) as fh:
            out.append(json.load(fh))
    return out


def markdown_table(results: list[dict]) -> str:
    lines = [
        "| arch | shape | compile(s) | mem GB/dev | coll GB/dev | "
        "compute ms | memory ms | collective ms | bottleneck | "
        "MODEL/HLO flops |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in results:
        if "skipped" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — |"
                         f" — | — | SKIP: {r['skipped'][:40]}… | — |")
            continue
        ms = r.get("mesh_single", {})
        rf = r.get("roofline", {})
        if not ms.get("ok"):
            lines.append(f"| {r['arch']} | {r['shape']} | FAIL | | | | | |"
                         f" {ms.get('error', '?')[:40]} | |")
            continue
        mem = (ms["memory"]["argument_bytes"]
               + ms["memory"]["temp_bytes"]) / 1e9
        coll = ms["collective_bytes"]["total"] / 1e9
        if "compute_s" in rf:
            ratio = rf.get("model_vs_hlo_flops")
            lines.append(
                f"| {r['arch']} | {r['shape']} | {ms['compile_s']} | "
                f"{mem:.1f} | {coll:.2f} | {rf['compute_s'] * 1e3:.1f} | "
                f"{rf['memory_s'] * 1e3:.1f} | "
                f"{rf['collective_s'] * 1e3:.1f} | {rf['bottleneck']} | "
                f"{ratio:.2f} |" if ratio else
                f"| {r['arch']} | {r['shape']} | {ms['compile_s']} | "
                f"{mem:.1f} | {coll:.2f} | | | | | |")
        else:
            lines.append(f"| {r['arch']} | {r['shape']} | "
                         f"{ms['compile_s']} | {mem:.1f} | {coll:.2f} | "
                         f"| | | (no roofline) | |")
    return "\n".join(lines)


def main(quick: bool = False, rows: Rows | None = None) -> list[dict]:
    rows = rows or Rows()
    results = load_all()
    ok = sum(1 for r in results if r.get("mesh_single", {}).get("ok")
             or "skipped" in r)
    multi_ok = sum(1 for r in results if r.get("mesh_multi", {}).get("ok")
                   or "skipped" in r)
    skipped = sum(1 for r in results if "skipped" in r)
    rows.add("roofline/combos_single_ok", 0.0,
             f"{ok}/{len(results)} (skips: {skipped})")
    rows.add("roofline/combos_multi_ok", 0.0, f"{multi_ok}/{len(results)}")
    for r in results:
        rf = r.get("roofline", {})
        if "bottleneck" in rf:
            rows.add(f"roofline/{r['arch']}/{r['shape']}", 0.0,
                     f"{rf['bottleneck']}-bound "
                     f"c={rf['compute_s'] * 1e3:.1f}ms "
                     f"m={rf['memory_s'] * 1e3:.1f}ms "
                     f"l={rf['collective_s'] * 1e3:.1f}ms")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(markdown_table(load_all()))
