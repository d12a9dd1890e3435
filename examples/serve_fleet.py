"""Field-validation analogue (§8.8): live scheduling of real JAX models.

Three reduced zoo models play the roles of the Ocularone DNNs — HV
(hazard-vest tracking, 10 FPS, tight deadline), DEV (distance estimation,
5 FPS), BP (body pose, 5 FPS; negative cloud utility like the paper's BP).
Each task is an actual jitted forward pass; the cloud path pays a shaped
network delay.  GEMS vs Edge-Only vs E+C, 20 s wall-clock each.

    PYTHONPATH=src python examples/serve_fleet.py --duration 20
"""
import argparse
import dataclasses

import numpy as np

from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.core.schedulers import make_policy
from repro.core.task import ModelProfile
from repro.serve.engine import ServableModel, ServeEngine, run_stream


def calibrate(run, n=30) -> float:
    import time
    ts = []
    for _ in range(n):
        t0 = time.monotonic()
        run()
        ts.append((time.monotonic() - t0) * 1e3)
    return float(np.percentile(ts, 95))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=20.0, help="seconds")
    ap.add_argument("--policies", default="EDF,EDF-E+C,GEMS")
    args = ap.parse_args()

    # role → (zoo family source, edge load share, deadline×t95, β, K, K̂)
    roles = {
        "HV": ("starcoder2-3b", 0.7, 3.0, 125, 1, 25),
        "DEV": ("granite-3-2b", 0.4, 5.0, 100, 1, 26),
        "BP": ("xlstm-1.3b", 0.3, 8.0, 40, 2, 43),   # γ^C < 0 → edge-only
    }
    models, fps = {}, {}
    for name, (arch, share, dl_mult, beta, ke, kc) in roles.items():
        cfg = reduced(ARCHS[arch], n_layers=2, d_model=192, vocab=512)
        prof = ModelProfile(name=name, beta=beta, deadline=1.0, t_edge=1.0,
                            t_cloud=1.0, cost_edge=ke, cost_cloud=kc,
                            qoe_beta=100.0, qoe_alpha=0.9,
                            qoe_window=5_000.0)
        sm = ServableModel.from_arch(prof, cfg, batch=1, seq=64)
        t95 = calibrate(sm.run)
        # load-calibrate: total demand ≈ 1.4× edge capacity so the
        # scheduler actually has decisions to make on this CPU
        fps[name] = min(60.0, share * 1000.0 / t95)
        prof = dataclasses.replace(prof, deadline=dl_mult * t95 + 30.0,
                                   t_edge=t95,
                                   t_cloud=t95 * 0.7 + 60.0)
        models[name] = dataclasses.replace(sm, profile=prof)
        print(f"{name:4s} ({arch}): edge p95 {t95:.1f} ms, cloud est "
              f"{prof.t_cloud:.1f} ms, deadline {prof.deadline:.0f} ms, "
              f"{fps[name]:.1f} FPS")
    duration_ms = args.duration * 1e3
    print()
    for pol in args.policies.split(","):
        engine = ServeEngine(make_policy(pol), dict(models),
                             cloud_concurrency=4, seed=0)
        # fresh stats per run
        r = run_stream(engine, fps, duration_ms)
        print(r.summary())
    print("\nGEMS keeps per-model completion-rate windows healthy by "
          "preemptively pushing lagging models' queued tasks to the cloud "
          "(paper §8.8: 48% more tasks than edge-only at 15 FPS).")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
