#!/usr/bin/env python3
"""The control of ``correct``: the plain reference in bfloat16, in the
program's place, must come out not correct.

    python3 bench/control.py --workload metro1024-steady-demsa \\
        --seeds 11 12 13 --ticks 640
    python3 bench/control.py --workload registry-sweep-warm \\
        --seeds 11 12 13 --ticks 12000

For each seed it builds the cell's traffic as a run does (on the device,
at the cell's size), steps the float32 reference and the bfloat16
control over the first ``--ticks`` ticks, and prints, per seed, every
number ``correct`` compares, read from the control against the
reference, beside the configuration's limit (a sweep's ``--ticks`` is the
length of each mission: 12000 is the whole 300 s).  The configuration states
float32 milliseconds, so bfloat16 is the precision below it.  The
benchmark's own runs never run this; it sets the upper readings the
limits are chosen under (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import ml_dtypes  # noqa: E402

from harness import check, gen  # noqa: E402

BF16 = ml_dtypes.bfloat16


def load(workload: str) -> tuple[dict, dict, dict]:
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((BENCH.parent / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def replay_readings(cfg, traffic, seed: int, ticks: int) -> dict:
    dt = cfg["scheduler"]["dt_ms"]
    n_edges, n_models = cfg["n_edges"], len(cfg["models"])
    call = traffic["call_ticks"]
    sig = gen.replay_signals(gen.seed_key(seed), traffic, n_edges, n_models,
                             dt)
    take = gen.window_fn(call, gen.horizon_ticks(traffic, dt), dt)
    segments = [jax.device_get(take(sig, np.int32(p)))
                for p in range(0, ticks, call)]
    del sig
    policy = traffic["policy"]
    want, arrived = check.ref_replay(cfg, policy, segments)
    got, _ = check.ref_replay(cfg, policy, segments, BF16)
    return dict(ledger_gap=check.ledger_gap(got, arrived),
                mismatch_pct=check.mismatch_pct(got, want, arrived))


def live_readings(cfg, traffic, seed: int, ticks: int) -> dict:
    dt = cfg["scheduler"]["dt_ms"]
    drones, phases = gen.fleet_draw(gen.seed_key(seed), traffic,
                                    cfg["n_edges"], dt)
    period = gen.period_ticks(traffic, dt)
    sched = gen.live_schedule(drones, phases, period, len(cfg["models"]))
    arrivals = [sched[t % period] for t in range(ticks)]
    want_rec, want, arrived = check.ref_live(cfg, traffic["policy"],
                                             arrivals, seed, ticks)
    got_rec, got, _ = check.ref_live(cfg, traffic["policy"], arrivals, seed,
                                     ticks, BF16)
    return dict(ledger_gap=check.ledger_gap(got, arrived),
                mismatch_pct=check.mismatch_pct(got, want, arrived),
                record_mismatch_pct=check.record_mismatch_pct(got_rec,
                                                              want_rec))


def sweep_readings(cfg, traffic, seed: int, ticks: int) -> dict:
    """The sweep's buckets lowered as a run lowers them, missions of
    ``ticks`` ticks; the bfloat16 reference against the float32 one."""
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.scenarios.compile import compile_registry_groups

    from harness import sweep

    dt = cfg["scheduler"]["dt_ms"]
    scenarios, policies, seeds, _ = sweep.plan(traffic, seed)
    buckets = [(jax.device_get(batch.signals._asdict()), rows)
               for batch, rows in compile_registry_groups(
                   scenarios, policies, seeds, dt=dt,
                   duration_ms=ticks * dt)]
    want = sweep.reference(cfg, buckets)
    got = sweep.reference(cfg, buckets, "bfloat16")
    gap, diff, arrived = 0, 0, 0
    for (_, _, w), (_, _, g) in zip(want, got):
        gap = max(gap, check.ledger_gap(g["outcome"], g["arrived"]))
        diff += check.mismatch_count(g["outcome"], w["outcome"])
        arrived += int(w["arrived"].sum())
    return dict(ledger_gap=gap, mismatch_pct=100.0 * diff / max(arrived, 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--ticks", type=int, required=True,
                    help="ticks to step: as many as a run of the cell steps")
    args = ap.parse_args(argv)
    cell, cfg, traffic = load(args.workload)
    read = dict(live=live_readings, replay=replay_readings,
                sweep=sweep_readings)[traffic["entry"]]
    print(f"device: {jax.devices()[0].device_kind} x{len(jax.devices())}",
          flush=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        got = read(cfg, traffic, seed, args.ticks)
        out = {k: {"value": v, "limit": cfg["limits"][k]}
               for k, v in got.items()}
        fails = any(v["value"] > v["limit"] for v in out.values())
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              ticks=args.ticks, control_fails=fails,
                              seconds=time.perf_counter() - t0,
                              readings=out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
