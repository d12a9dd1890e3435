"""Replay entry: a planner steps a city-scale mission on the chip.

Set-up builds the traffic on the device, the model table, the policy and
one donated fleet program with its state, and steps the first call of the
mission through the window's own call, which compiles every program the
window runs.  The window then calls ``FleetProgram.run`` (donated,
``chunk_ticks`` from the traffic file) on consecutive segments of the
mission, carrying the state, until ``--seconds`` have passed; the last
call issued inside them completes and counts.  At most two calls are in
flight.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from harness import gen


def _program(cell):
    from repro.core.task import ModelProfile
    from repro.sim import fleet_jax as fj

    cfg, traffic = cell.cfg, cell.traffic
    s = cfg["scheduler"]
    cell.require_program_matches(fj)
    models = [ModelProfile(m["name"], m["beta"], m["deadline_ms"],
                           m["t_edge_ms"], m["t_cloud_ms"], m["cost_edge"],
                           m["cost_cloud"]) for m in cfg["models"]]
    policy = fj.FleetPolicy.from_name(traffic["policy"])
    prog = fj.FleetProgram.for_policy(
        policy, dt=s["dt_ms"], edge_frac=s["edge_frac"],
        cloud_frac=s["cloud_frac"], donate=True)
    prof = fj.Profiles.build(models)
    state = prog.init(prof, policy, cfg["n_edges"], s["cloud_slots"])
    return fj, prog, prof, policy.params(), state


def outcome(state) -> dict:
    """The program's integer end state per edge, on the host."""
    g = jax.device_get(dict(
        n_success=state.n_success, n_miss=state.n_miss,
        n_drop=state.n_drop, n_stolen=state.n_stolen,
        n_edge_exec=state.n_edge_exec, n_peer_out=state.n_peer_out,
        n_peer_in=state.n_peer_in, eq_valid=state.eq.valid,
        cq_valid=state.cq.valid))
    out = {k: np.asarray(v) for k, v in g.items()
           if k not in ("eq_valid", "cq_valid")}
    out["eq_depth"] = np.asarray(g["eq_valid"]).sum(-1).astype(np.int32)
    out["cq_depth"] = np.asarray(g["cq_valid"]).sum(-1).astype(np.int32)
    return out


def run(cell) -> dict:
    cfg, traffic = cell.cfg, cell.traffic
    dt = cfg["scheduler"]["dt_ms"]
    n_edges, n_models = cfg["n_edges"], len(cfg["models"])
    call_ticks, chunk = traffic["call_ticks"], traffic["chunk_ticks"]
    horizon = gen.horizon_ticks(traffic, dt)
    with cell.setup_span():
        fj, prog, prof, pp, state = _program(cell)
        cell.mark("program and state built")
        sig = gen.replay_signals(cell.key(), traffic, n_edges, n_models, dt)
        jax.block_until_ready(sig)
        cell.mark("traffic built on the device")
        take = gen.window_fn(call_ticks, horizon, dt)

        def call(st, start):
            win = fj.FleetSignals(**take(sig, np.int32(start)))
            return prog.run(prof, pp, st, win, chunk_ticks=chunk)

        # the window's own call, on the mission's first segment: compiles
        # (or loads) every program the window runs
        state = jax.block_until_ready(call(state, 0))
        pos = call_ticks
        cell.mark("first call stepped")

    calls = 0
    with cell.window() as w:
        prev = None
        while time.perf_counter() - w.t0 < cell.seconds:
            with w.span("bench.issue"):
                out = call(state, pos)
            if prev is not None:
                with w.span("bench.wait"):
                    jax.block_until_ready(prev)
            prev = state = out
            pos += call_ticks
            calls += 1
        with w.span("bench.wait"):
            jax.block_until_ready(state)
    ticks = calls * call_ticks
    cell.metric("edge_ticks_per_s", ticks * n_edges / w.seconds,
                "edge-ticks/s")
    cell.say(f"replay: {calls} calls of {call_ticks} ticks x {n_edges} "
             f"edges in {w.seconds:.6f} s; real-time factor "
             f"{ticks * dt / 1e3 / w.seconds:.4f}")
    cell.layer.update(ticks=ticks, calls=calls, call_ticks=call_ticks,
                      n_edges=n_edges, coop=traffic["policy"].endswith(
                          "-COOP"), substeps=cfg["scheduler"]["substeps"],
                      cloud_cap=cfg["scheduler"]["cloud_queue_cap"],
                      edge_cap=cfg["scheduler"]["edge_queue_cap"],
                      coop_rounds=cfg["scheduler"]["coop_max_transfers"])
    cell.read_memory(jax.devices()[:cell.chips])

    # -- correctness: the end state against the plain reference ----------
    got = outcome(state)
    peer = int(got["n_peer_out"].sum())
    cell.say(f"peer_out per simulated second: "
             f"{peer / (pos * dt / 1e3):.4f} ({peer} over {pos} ticks)")
    segments = [jax.device_get(take(sig, np.int32(p)))
                for p in range(0, pos, call_ticks)]
    del state, prev, out, sig
    cell.free_device()
    cell.compare_replay(got, segments)
    return {"attempted": calls, "failed": 0}

