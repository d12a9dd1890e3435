"""Sweep entry on a mesh: ``harness/sweep.py``'s planner sweep, with
each bucket fanned over the host's chips by ``run_registry_sweep``'s
``mesh`` (the configuration's ``deployment.mesh``, ``"auto"``: a
bucket's replica axis over the largest number of the chips that divides
its lanes).

Set-up makes one pass, the window's own call, which compiles (or loads)
every sharded program a pass runs.  The window calls
``run_registry_sweep`` pass after pass until ``--seconds`` have passed;
the last pass issued inside them completes and counts.  The window's
passes fill a :class:`repro.obs.prof.SweepStats` record, whose sums the
per-layer readers read: the lowering, each bucket from dispatch to a
ready state, the gather of its sharded end state to the host, and
``summarize``.  The sweep's calls into the program are watched as in
``harness/sweep.py``, to keep the last pass's buckets and end states.
A traced run keeps the same slice as there, the lowering and the start
of the first bucket; the profiler's stop then holds the host inside that
bucket (60.3 s on a four-chip v5e, more than the rest of the bucket), so
the time a bucket-tick takes is read over the other buckets only.

``correct`` is ``harness/sweep.py``'s: the plain reference over every
tick of every bucket of the last pass, and the rows against its end
states.
"""
from __future__ import annotations

import time

import jax

from harness import sweep
from harness.replay import outcome


def _require_fanned_out(cell, stats) -> None:
    """Every bucket spanned the largest number of the cell's chips that
    divides its lanes, as the configuration's mesh says."""
    bad = [(b.lanes, b.chips) for b in stats.buckets
           if b.chips != max(d for d in range(1, cell.chips + 1)
                             if b.lanes % d == 0)]
    if bad:
        raise ValueError(f"buckets did not fan over the chips the "
                         f"configuration's mesh gives them "
                         f"(lanes, chips): {bad}")


def run(cell) -> dict:
    # a program without the sweep's record cannot run this cell: it
    # fails here, before set-up
    from repro.obs.prof import SweepStats
    from repro.scenarios.runner import run_registry_sweep
    from repro.sim import fleet_jax as fj

    dt = cell.cfg["scheduler"]["dt_ms"]
    mesh = cell.cfg["deployment"]["mesh"]
    scenarios, policies, seeds, duration_ms = sweep.plan(cell.traffic,
                                                         cell.seed)

    def one_pass(stats):
        return run_registry_sweep(scenarios, policies, seeds, dt=dt,
                                  duration_ms=duration_ms, mesh=mesh,
                                  stats=stats)

    with cell.setup_span():
        cell.require_program_matches(fj)
        first = SweepStats()
        with sweep.watched() as seen:
            one_pass(first)
        sweep._require_library_matches(cell, seen.groups, policies, seeds,
                                       duration_ms)
        _require_fanned_out(cell, first)
        del seen
        cell.mark(f"first pass swept, chips a bucket "
                  f"{[b.chips for b in first.buckets]}")

    stats, passes, rows, held = SweepStats(), 0, [], []

    def end_trace():
        # as harness/sweep.py: the trace keeps the lowering and the
        # start of the first bucket, which holds the profiler's stop
        held.append(len(stats.buckets))
        time.sleep(sweep.TRACED_BUCKET_S)
        cell.stop_trace()

    with cell.window() as w, sweep.watched(
            w.span, end_trace if cell.trace else None) as seen:
        while time.perf_counter() - w.t0 < cell.seconds:
            with w.span("bench.issue"):
                rows = one_pass(stats)
            passes += 1
    cell.metric("sweep_s", w.seconds / passes, "s")
    cell.read_memory(jax.devices()[:cell.chips])
    kept = [(outcome(final), jax.device_get(batch.signals._asdict()), brows)
            for final, batch, brows in seen.buckets()]
    del seen
    cell.free_device()
    lanes = sum(k[1]["arrive"].shape[0] for k in kept)
    clean = [b for i, b in enumerate(stats.buckets) if i not in held]
    cell.layer.update(passes=passes, runs=len(rows), buckets=len(kept),
                      lanes=lanes, ticks=stats.bucket_ticks,
                      sweep_lower_s=stats.lower_s,
                      sweep_bucket_s=stats.run_s,
                      sweep_gather_s=stats.gather_s,
                      sweep_clean_bucket_s=sum(b.run_s for b in clean),
                      sweep_clean_bucket_ticks=sum(b.ticks for b in clean))
    cell.say(f"sweep: {passes} passes of {len(rows)} runs in {len(kept)} "
             f"buckets ({lanes} lanes) in {w.seconds:.6f} s; lowering "
             f"{stats.lower_s:.6f} s, buckets {stats.run_s:.6f} s over "
             f"{stats.bucket_ticks} bucket-ticks, gather "
             f"{stats.gather_s:.6f} s, summarize {stats.summarize_s:.6f} s")
    wrong = sweep.compare(cell, kept, rows)
    return {"attempted": passes * len(rows), "failed": wrong}
