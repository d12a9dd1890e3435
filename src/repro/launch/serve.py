"""Serving launcher: the paper's scheduler over live model inference.

``python -m repro.launch.serve --policy GEMS --duration 15`` registers
three reduced zoo models as the Ocularone DNS (HV/DEV/BP roles), measures
their p95 latencies, and streams frame-rate tasks through the chosen
policy — the §8.8 field validation without a drone.

Two backends share the measured profiles:

* ``--backend thread`` (default) — the Python :class:`~repro.serve.
  engine.ServeEngine`: real jitted forward passes execute on a worker
  thread per task.
* ``--backend fleet`` — the compiled online control plane
  (:class:`repro.serve.controller.FleetController`): the same frame
  stream is scheduled by the jitted tick program window-by-window, with
  per-tick decision records, flight-recorder tails, and checkpointed
  crash restart (``--checkpoint``).  ``--snapshot-out`` dumps the final
  ``metrics_snapshot()`` as JSON (the CI smoke artifact).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import signal
import time

import numpy as np

from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.core.schedulers import ALL_POLICIES, make_policy
from repro.core.task import ModelProfile
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import ServableModel, ServeEngine, run_stream


def probe_p95(model: ServableModel, iters: int = 20) -> float:
    """Warm up + measure a servable model's p95 latency [ms].

    The common calibration both backends build their profiles from: the
    first call hits any residual compile cost, so the percentile is
    taken over ``iters`` steady-state invocations.
    """
    ts = []
    for _ in range(iters):
        t0 = time.monotonic()
        model.run()
        ts.append((time.monotonic() - t0) * 1e3)
    return float(np.percentile(ts, 95))


def build_roles(cloud_concurrency: int = 4
                ) -> tuple[dict[str, ServableModel], dict[str, float]]:
    """Register the Ocularone DNS roles and calibrate their profiles.

    Returns ``(models, fps)``: servable models re-profiled from their
    measured p95 (deadline, edge/cloud latencies) and each role's target
    frame rate.
    """
    roles = {"HV": ("starcoder2-3b", 0.7, 3.0, 125, 1, 25),
             "DEV": ("granite-3-2b", 0.4, 5.0, 100, 1, 26),
             "BP": ("xlstm-1.3b", 0.3, 8.0, 40, 2, 43)}
    models, fps = {}, {}
    for name, (arch, share, dlm, beta, ke, kc) in roles.items():
        cfg = reduced(ARCHS[arch], n_layers=2, d_model=192, vocab=512)
        prof = ModelProfile(name=name, beta=beta, deadline=1.0, t_edge=1.0,
                            t_cloud=1.0, cost_edge=ke, cost_cloud=kc,
                            qoe_beta=100.0, qoe_alpha=0.9,
                            qoe_window=5_000.0)
        sm = ServableModel.from_arch(prof, cfg, batch=1, seq=64)
        t95 = probe_p95(sm)
        fps[name] = min(60.0, share * 1000.0 / t95)
        prof = dataclasses.replace(prof, deadline=dlm * t95 + 30.0,
                                   t_edge=t95, t_cloud=t95 * 0.7 + 60.0)
        models[name] = dataclasses.replace(sm, profile=prof)
        print(f"{name}: p95 {t95:.1f} ms, {fps[name]:.1f} FPS, "
              f"deadline {prof.deadline:.0f} ms")
    return models, fps


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="GEMS", choices=list(ALL_POLICIES))
    ap.add_argument("--duration", type=float, default=15.0)
    ap.add_argument("--cloud-concurrency", type=int, default=4)
    ap.add_argument("--backend", default="thread",
                    choices=("thread", "fleet"),
                    help="thread = Python ServeEngine with live forward "
                         "passes; fleet = compiled FleetController")
    ap.add_argument("--edges", type=int, default=2,
                    help="[fleet] number of edges in the fleet")
    ap.add_argument("--checkpoint", default=None,
                    help="[fleet] checkpoint path stem for crash restart")
    ap.add_argument("--snapshot-out", default=None,
                    help="[fleet] write the final metrics_snapshot() JSON")
    args = ap.parse_args()

    models, fps = build_roles(args.cloud_concurrency)

    if args.backend == "fleet":
        from repro.serve.controller import FleetController, drive_stream
        ctl = FleetController(
            [m.profile for m in models.values()], args.policy,
            n_edges=args.edges, cloud_slots=args.cloud_concurrency,
            checkpoint_path=args.checkpoint)
        # graceful shutdown: first SIGINT/SIGTERM stops the stream at
        # the next poll; drive_stream still flushes buffered ticks and
        # writes the final checkpoint, and the snapshot below is dumped
        # as on a normal exit.  A second signal interrupts hard.
        interrupted = []

        def _graceful(signum, frame):
            if interrupted:
                raise KeyboardInterrupt
            interrupted.append(signum)
            print(f"signal {signum}: draining — final checkpoint and "
                  f"snapshot on the way (repeat to force-quit)")

        previous = {s: signal.signal(s, _graceful)
                    for s in (signal.SIGINT, signal.SIGTERM)}
        try:
            snap = drive_stream(ctl, fps, args.duration * 1e3,
                                stop=lambda: bool(interrupted))
        finally:
            for s, h in previous.items():
                signal.signal(s, h)
        if args.snapshot_out:
            with open(args.snapshot_out, "w") as f:
                json.dump(snap, f, indent=2, default=float)
        print(json.dumps(
            {k: snap[k] for k in ("policy", "completed", "missed",
                                  "dropped", "completion_rate",
                                  "windows_run", "step_latency_ms")},
            indent=2, default=float))
        return

    engine = ServeEngine(make_policy(args.policy), models,
                         cloud_concurrency=args.cloud_concurrency)
    result = run_stream(engine, fps, args.duration * 1e3)
    print(result.summary())


if __name__ == "__main__":
    main()
