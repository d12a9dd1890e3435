"""The plain reference over stacked lanes, in worker processes.

A job is lanes of one bucket that run one policy: their signals
``[R, T, E, ...]``, each lane's FaaS slot count, and the policy.  Lanes
are independent of one another (a lane is a run, or one edge of a run
whose edges do not interact), so a job steps its lanes' edges as one
stacked reference, each lane a run of its own for the peer exchange.

:func:`run_jobs` hands each job to a fresh ``python3`` that imports only
NumPy and the reference (never JAX, so the chip stays with the parent)
and reads the job from its standard input and writes the outcome to its
standard output.
"""
from __future__ import annotations

import os
import pickle
import subprocess
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the signals the reference reads, per tick
_PER_EDGE = ("theta", "bw", "arrive", "order", "load_mult", "valid",
             "exec_jit", "edge_up", "link_up")


def job(cfg: dict, policy: str, sig: dict, lanes, slots, dtype="float32"
        ) -> dict:
    """The job for ``lanes`` (indices into ``sig``'s leading axis), with
    ``slots[k]`` the FaaS slots of lane ``lanes[k]``."""
    lanes = np.asarray(lanes)
    return dict(cfg=cfg, policy=policy, dtype=dtype,
                slots=np.asarray(slots, np.int32),
                sig={k: np.asarray(v)[lanes] for k, v in sig.items()})


def step(j: dict) -> dict:
    """Step one job's reference over every tick; returns its outcome per
    edge, lanes' edges in order (``[R * E, ...]``), and the tasks that
    arrived on each."""
    sys.path.insert(0, BENCH)
    import ml_dtypes

    from harness import check

    sig, cfg = j["sig"], j["cfg"]
    r, t_n, e, m = sig["arrive"].shape
    times = sig["times"]
    if not np.array_equal(times, np.broadcast_to(times[0], times.shape)):
        raise ValueError("the lanes of a job must share their tick times")
    flat = {k: np.ascontiguousarray(np.moveaxis(sig[k], 1, 0).reshape(
        (t_n, r * e) + sig[k].shape[3:])) for k in _PER_EDGE}
    cloud_up = np.repeat(np.asarray(sig["cloud_up"]).T, e, axis=1)
    dtype = ml_dtypes.bfloat16 if j["dtype"] == "bfloat16" else np.float32
    table = dict(cfg, models=cfg["models"][:m])
    ref = check.reference(table, j["policy"], r * e, dtype,
                          slots=np.repeat(j["slots"], e),
                          groups=np.repeat(np.arange(r), e))
    for t in range(t_n):
        x = {k: v[t] for k, v in flat.items()}
        x["now"], x["cloud_up"] = times[0, t], cloud_up[t]
        ref.step(x)
    return dict(outcome=ref.outcome(),
                arrived=flat["arrive"].sum(axis=(0, 2)))


def run_jobs(jobs: list[dict], workers: int) -> list[dict]:
    """Every job's :func:`step`, in order; at most ``workers`` processes
    at a time, each waited for."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out: list = [None] * len(jobs)
    todo = list(range(len(jobs)))
    running: list = []
    try:
        while todo or running:
            while todo and len(running) < workers:
                i = todo.pop(0)
                p = subprocess.Popen([sys.executable, __file__],
                                     stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, env=env)
                running.append((i, p))
                p.stdin.write(pickle.dumps(jobs[i], protocol=5))
                p.stdin.close()
            i, p = running.pop(0)
            data = p.stdout.read()
            if p.wait() != 0:
                raise RuntimeError(f"reference job {i} failed "
                                   f"(exit {p.returncode})")
            out[i] = pickle.loads(data)
    finally:
        for _, p in running:
            p.kill()
            p.wait()
    return out


def main() -> int:
    j = pickle.loads(sys.stdin.buffer.read())
    sys.stdout.buffer.write(pickle.dumps(step(j), protocol=5))
    return 0


if __name__ == "__main__":
    sys.exit(main())
