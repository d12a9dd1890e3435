"""Live entry: a control plane feeds telemetry on the wall clock.

An open loop: tick ``k`` of the mission is due at ``t0 + k * dt`` on the
host clock, whether or not the controller has kept up.  At each due time
the loop submits that tick's arrivals through ``FleetController.submit``
and calls ``poll``, which steps every complete decision window.  A
window's decision is due when its last tick ends; its latency runs from
that due time to the moment ``poll`` has returned its records.  Set-up
drives the same controller through ``warmup_ticks`` of the mission
without the clock, which compiles its window program, and the window
carries on from there.
"""
from __future__ import annotations

import time

import numpy as np

from harness import check, gen


def run(cell) -> dict:
    from repro.core.task import ModelProfile
    from repro.serve.controller import FleetController
    from repro.sim import fleet_jax as fj
    import jax

    cfg, traffic = cell.cfg, cell.traffic
    s = cfg["scheduler"]
    dt, n_edges, n_models = s["dt_ms"], cfg["n_edges"], len(cfg["models"])
    wt = cfg["window_ticks"]
    period = gen.period_ticks(traffic, dt)
    warm = traffic["warmup_ticks"]
    if warm % wt:
        raise ValueError("warmup_ticks must be whole decision windows")
    # every tick of the run fits in the controller's logs
    log = warm + int(cell.seconds * 1e3 / dt) + 8 * wt
    with cell.setup_span():
        cell.require_program_matches(fj)
        models = [ModelProfile(m["name"], m["beta"], m["deadline_ms"],
                               m["t_edge_ms"], m["t_cloud_ms"],
                               m["cost_edge"], m["cost_cloud"])
                  for m in cfg["models"]]
        ctl = FleetController(
            models, traffic["policy"], n_edges=n_edges, dt=dt,
            window_ticks=wt, cloud_slots=s["cloud_slots"],
            edge_frac=s["edge_frac"], cloud_frac=s["cloud_frac"],
            order_seed=cell.seed, decision_log=log, latency_log=log)
        cell.mark("controller built")
        drones, phases = gen.fleet_draw(cell.key(), traffic, n_edges, dt)
        sched = gen.live_schedule(drones, phases, period, n_models)
        for k in range(warm):
            for e, m in sched[k % period]:
                ctl.submit(k * dt, e, m)
            ctl.poll(k * dt)
        ctl.reset_latency_stats()
        cell.mark(f"{warm} warm-up ticks stepped")

    late, lat, host = [], [], []
    refused = 0
    with cell.window() as w:
        k = warm
        while True:
            due = w.t0 + (k - warm) * dt / 1e3
            if due - w.t0 >= cell.seconds:
                break
            with w.span("bench.sleep"):
                pause = due - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
            late.append((time.perf_counter() - due) * 1e3)
            with w.span("bench.submit"):
                for e, m in sched[k % period]:
                    refused += ctl.submit(k * dt, e, m) < 0
            n0, first = len(ctl.step_latencies_ms), ctl.tick
            t_poll = time.perf_counter()
            with w.span("bench.poll"):
                ctl.poll(k * dt)
            done = time.perf_counter()
            steps = ctl.step_latencies_ms[n0:]
            if steps:
                host.append((done - t_poll) * 1e3 - sum(steps))
            for end in range(first + wt, ctl.tick + 1, wt):
                lat.append((done - (w.t0 + (end - warm) * dt / 1e3)) * 1e3)
            k += 1
    step_ms = ctl.step_latencies_ms
    cell.metric("decision_ms_p95", np.percentile(lat, 95), "ms")
    cell.say(f"live: {len(lat)} decision windows of {wt} ticks, "
             f"{n_edges} edges; decision ms p50 {np.percentile(lat, 50):.6f}"
             f" p95 {np.percentile(lat, 95):.6f} max {max(lat):.6f}")
    cell.say(f"generator lateness ms: p50 {np.percentile(late, 50):.6f} "
             f"max {max(late):.6f} over {len(late)} ticks; "
             f"{refused} submissions refused")
    cell.layer.update(ctl_step_ms=list(step_ms), ctl_host_ms=host,
                      windows=len(lat), ticks=ctl.tick - warm,
                      n_edges=n_edges)
    cell.read_memory(jax.devices()[:cell.chips])

    # -- correctness: decision records and end state vs the reference ----
    n_ticks = ctl.tick
    got_records = list(ctl.decisions)
    from harness.replay import outcome
    got = outcome(ctl.state)
    del ctl
    cell.free_device()
    arrivals = [sched[t % period] for t in range(n_ticks)]
    t0 = time.perf_counter()
    want_records, want, arrived = check.ref_live(
        cfg, traffic["policy"], arrivals, cell.seed, n_ticks)
    cell.say(f"reference: {n_edges} edges over {n_ticks} ticks in "
             f"{time.perf_counter() - t0:.3f} s")
    cell.record("ledger_gap", check.ledger_gap(got, arrived))
    cell.record("mismatch_pct", check.mismatch_pct(got, want, arrived))
    cell.record("record_mismatch_pct",
                check.record_mismatch_pct(got_records, want_records))
    return {"attempted": len(lat), "failed": refused}
