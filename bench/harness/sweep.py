"""Sweep entry: a planner sweeps scenarios x policies x seeds through
``run_registry_sweep``.

Set-up makes one pass, the window's own call, which compiles (or loads)
every program a pass runs.  The window calls ``run_registry_sweep`` one
pass at a time until ``--seconds`` have passed; the last pass issued
inside them completes and counts.  The sweep's calls into the program
are watched (:func:`watched`): the host clock is read round its lowering
(``compile_registry_groups``) and round each bucket's ``run_batch`` (its
dispatch and device work), for the per-layer shares, and the last pass's
buckets and end states are kept.

``correct`` steps the plain reference over every tick of every bucket of
that pass, in worker processes (``harness/ref_pool.py``), and compares
its end states with it per edge and model; ``row_gap`` compares the rows
the pass returned with the totals of those end states.
"""
from __future__ import annotations

import contextlib
import math
import os
import time

import jax
import numpy as np

from harness import check, ref_pool
from harness.replay import outcome

# each row's integer totals, and the end-state counter each one sums
ROW_FIELDS = dict(completed="n_success", missed="n_miss", dropped="n_drop",
                  stolen="n_stolen", peer_offloaded="n_peer_out")
# edges a reference job steps at most
JOB_EDGES = 32
# seconds of the first bucket a traced run keeps in its profile
TRACED_BUCKET_S = 0.5


def plan(traffic: dict, seed: int) -> tuple:
    """``(scenarios, policies, seeds, duration_ms)`` of a traffic file:
    ``seeds`` consecutive scenario seeds from ``seed`` mod 2**31."""
    scenarios = traffic["scenarios"]
    first = seed % 2**31
    return (None if scenarios == "all" else tuple(scenarios),
            tuple(traffic["policies"]),
            tuple(first + i for i in range(int(traffic["seeds"]))),
            traffic.get("duration_ms"))


def _require_library_matches(cell, groups, policies, seeds, duration_ms):
    """Every run the lowering made has the shape the configuration states:
    edges, models, FaaS slots and ticks, one row per (scenario, policy,
    seed)."""
    lib, dt = cell.cfg["scenarios"], cell.cfg["scheduler"]["dt_ms"]
    want = {(sc, p, s) for sc in lib for p in policies for s in seeds}
    if cell.traffic["scenarios"] != "all":
        want = {k for k in want if k[0] in cell.traffic["scenarios"]}
    seen, bad = set(), []
    for batch, rows in groups:
        _, t_n, e, m = batch.signals.arrive.shape
        slots = np.asarray(jax.device_get(batch.state.n_slots))[:, 0]
        for row in rows:
            seen.add((row.scenario, row.policy, row.seed))
            have = dict(n_edges=e * len(row.lanes), n_models=m,
                        cloud_slots=set(slots[list(row.lanes)].tolist()),
                        ticks=t_n)
            sc = lib.get(row.scenario)
            if sc is None or have != dict(
                    n_edges=sc["n_edges"], n_models=sc["n_models"],
                    cloud_slots={sc["cloud_slots"]},
                    ticks=round((duration_ms or sc["horizon_ms"]) / dt)):
                bad.append((row.scenario, have))
    if bad or seen != want:
        raise ValueError(f"the sweep's runs differ from the configuration: "
                         f"{bad[:4]}, rows missing {sorted(want - seen)[:4]}, "
                         f"not configured {sorted(seen - want)[:4]}")


class Watch:
    """What the sweep's calls into the program did in the last pass: the
    lowering's buckets ``(batch, rows)``, each bucket's end state, and
    the host seconds spent in both."""

    def __init__(self):
        self.groups, self.finals = [], {}
        self.spent = dict(lower=0.0, bucket=0.0)
        self.dispatched = False

    def buckets(self) -> list[tuple]:
        """The last pass's ``(end state, batch, rows)`` of each bucket."""
        return [(self.finals[id(b)], b, rows) for b, rows in self.groups]


@contextlib.contextmanager
def watched(span=lambda name: contextlib.nullcontext(), dispatched=None):
    """Run ``run_registry_sweep`` under a :class:`Watch`: its lowering
    (``compile_registry_groups``) and each bucket's ``run_batch`` are
    wrapped, the host clock read round each (``run_batch`` until its
    state is ready), each under ``span("bench.sweep.<lower|bucket>")``;
    ``dispatched()`` is called once, when the first bucket has been
    dispatched and the host would wait for it.  ``run_registry_sweep``
    imports both from their modules at each call, so it calls the
    wrappers, which return what the originals return."""
    import repro.scenarios.compile as comp
    import repro.sim.fleet_jax as fj

    lower, run_batch = comp.compile_registry_groups, fj.run_batch
    seen = Watch()

    def timed_lower(*a, **k):
        seen.groups, seen.finals = [], {}     # the pass before is done
        t0 = time.perf_counter()
        with span("bench.sweep.lower"):
            groups = lower(*a, **k)
        seen.spent["lower"] += time.perf_counter() - t0
        seen.groups = groups
        return groups

    def timed_run_batch(batch, *a, **k):
        t0 = time.perf_counter()
        with span("bench.sweep.bucket"):
            out = run_batch(batch, *a, **k)
            if dispatched is not None and not seen.dispatched:
                seen.dispatched = True
                dispatched()
            out = jax.block_until_ready(out)
        seen.spent["bucket"] += time.perf_counter() - t0
        seen.finals[id(batch)] = out
        return out

    comp.compile_registry_groups, fj.run_batch = timed_lower, timed_run_batch
    try:
        yield seen
    finally:
        comp.compile_registry_groups, fj.run_batch = lower, run_batch


def reference(cfg: dict, buckets, dtype: str = "float32") -> list[tuple]:
    """The plain reference over every bucket's ``(signals, rows)``:
    ``(bucket, lanes, {"outcome", "arrived"})`` for each job, a job being
    a bucket's lanes of one policy, at most :data:`JOB_EDGES` edges."""
    lib = cfg["scenarios"]
    jobs = []
    for b, (sig, rows) in enumerate(buckets):
        e = sig["arrive"].shape[2]
        for policy in sorted({r.policy for r in rows}):
            lanes = [(lane, lib[r.scenario]["cloud_slots"]) for r in rows
                     if r.policy == policy for lane in r.lanes]
            n = math.ceil(len(lanes) * e / JOB_EDGES)
            for part in np.array_split(np.arange(len(lanes)), n):
                ids = [lanes[i][0] for i in part]
                jobs.append((ref_pool.job(cfg, policy, sig, ids,
                                          [lanes[i][1] for i in part],
                                          dtype), b, ids))
    workers = min(len(jobs), max(1, (os.cpu_count() or 2) - 2))
    done = ref_pool.run_jobs([j for j, _, _ in jobs], workers)
    return [(b, ids, res) for (_, b, ids), res in zip(jobs, done)]


def edges(got: dict, lanes) -> dict:
    """The end state of ``lanes``, their edges stacked in order."""
    return {k: v[lanes].reshape((-1,) + v.shape[2:]) for k, v in got.items()}


def compare(cell, kept, rows) -> int:
    """Record ``ledger_gap``, ``mismatch_pct`` and ``row_gap`` for a
    pass's ``(end state, signals, rows)`` of each bucket and the rows it
    returned; returns the rows that differ from the end states."""
    t0 = time.perf_counter()
    want = reference(cell.cfg, [(sig, brows) for _, sig, brows in kept])
    gap, diff, arrived = 0, 0, 0
    for b, lanes, res in want:
        got = edges(kept[b][0], lanes)
        gap = max(gap, check.ledger_gap(got, res["arrived"]))
        diff += check.mismatch_count(got, res["outcome"])
        arrived += int(res["arrived"].sum())
    ticks = sum(k[1]["arrive"].shape[1] for k in kept)
    cell.say(f"reference: {len(want)} jobs over {ticks} bucket-ticks in "
             f"{time.perf_counter() - t0:.3f} s")
    cell.record("ledger_gap", gap)
    cell.record("mismatch_pct", 100.0 * diff / max(arrived, 1))

    totals = {}
    for got, _, brows in kept:
        for r in brows:
            part = edges(got, list(r.lanes))
            totals[r.scenario, r.policy, r.seed] = {
                f: int(part[c].sum()) for f, c in ROW_FIELDS.items()}
    have = {(r["scenario"], r["policy"], r["seed"]): r for r in rows}
    worst, wrong = 0, 0
    for key in totals.keys() | have.keys():
        if key not in totals or key not in have:
            d = max(arrived, 1)
        else:
            d = max(abs(int(have[key][f]) - totals[key][f])
                    for f in ROW_FIELDS)
        worst, wrong = max(worst, d), wrong + (d > 0)
    cell.record("row_gap", worst)
    return wrong


def run(cell) -> dict:
    from repro.scenarios.runner import run_registry_sweep
    from repro.sim import fleet_jax as fj

    dt = cell.cfg["scheduler"]["dt_ms"]
    scenarios, policies, seeds, duration_ms = plan(cell.traffic, cell.seed)

    def sweep():
        return run_registry_sweep(scenarios, policies, seeds, dt=dt,
                                  duration_ms=duration_ms)

    with cell.setup_span():
        cell.require_program_matches(fj)
        # the window's own call: compiles (or loads) every program a pass
        # runs, and warms the lowering
        with watched() as seen:
            sweep()
        _require_library_matches(cell, seen.groups, policies, seeds,
                                 duration_ms)
        del seen
        cell.mark("first pass swept")

    def end_trace():
        # a pass runs about a million device operations a second for
        # some 90 s, which no profile keeps (it holds ~6 M, and stopping
        # takes ~30 s a million): the trace holds the lowering and the
        # start of the first bucket, while the host waits for that bucket
        time.sleep(TRACED_BUCKET_S)
        cell.stop_trace()

    passes, rows = 0, []
    with cell.window() as w, watched(
            w.span, end_trace if cell.trace else None) as seen:
        while time.perf_counter() - w.t0 < cell.seconds:
            with w.span("bench.issue"):
                rows = sweep()
            passes += 1
    cell.metric("sweep_s", w.seconds / passes, "s")
    cell.read_memory(jax.devices()[:cell.chips])
    # what the last pass produced, for the check
    kept = [(outcome(final), jax.device_get(batch.signals._asdict()), brows)
            for final, batch, brows in seen.buckets()]
    spent = seen.spent
    del seen
    cell.free_device()
    ticks = sum(k[1]["arrive"].shape[1] for k in kept)
    lanes = sum(k[1]["arrive"].shape[0] for k in kept)
    cell.layer.update(passes=passes, runs=len(rows), buckets=len(kept),
                      lanes=lanes, ticks=ticks * passes,
                      sweep_lower_s=spent["lower"],
                      sweep_bucket_s=spent["bucket"])
    cell.say(f"sweep: {passes} passes of {len(rows)} runs in {len(kept)} "
             f"buckets ({lanes} lanes, {ticks} bucket-ticks a pass) in "
             f"{w.seconds:.6f} s; lowering {spent['lower']:.6f} s, buckets "
             f"{spent['bucket']:.6f} s")
    wrong = compare(cell, kept, rows)
    return {"attempted": passes * len(rows), "failed": wrong}
