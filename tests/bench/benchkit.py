"""Helpers for the benchmark's CPU tests: tiny cells driven in-process.

The harness refuses to run without a TPU; these helpers skip only that
look for a chip and drive the rest of a run through ``run_cell``.
"""
from __future__ import annotations

import copy
import importlib.util
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

FAKE_DEVICE = dict(platform="cpu", kind="TPU v5 lite", count=1)
SEED = 2**33 + 12345        # seeds may exceed 32 bits

# No committed cell runs cooperation; the tests keep its reference and
# its faults covered on this mix: one edge in eight with four drones, the
# others with two, under DEMS-A-COOP, on the steady cell's configuration.
COOP_MIX = "hotspot-4da-2da"
COOP_CELL = "metro1024-hotspot-coop"
COOP_TRAFFIC = dict(policy="DEMS-A-COOP", drones_per_edge=2,
                    hot_fraction=0.125, hot_drones_per_edge=4)


def traffic(name: str, **kw) -> dict:
    """A committed traffic file's parameters (or the tests' COOP mix),
    updated with ``kw``."""
    import json

    base = "steady-3da" if name == COOP_MIX else name
    t = json.loads((ROOT / "bench" / "traffic" / f"{base}.json").read_text())
    if name == COOP_MIX:
        t.update(COOP_TRAFFIC)
    t.update(kw)
    return t


def bench_run():
    """``bench/run.py`` as a module (loaded once per process)."""
    if "bench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "bench_run", ROOT / "bench" / "run.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_run"]


def tiny_cell(workload: str, *, n_edges: int = 8, horizon_ms: float = 4000.0,
              seconds: float = 0.5, seed: int = SEED, trace: bool = False,
              chips: int = 1):
    """The cell at a size a test can hold: fewer edges, a short mission."""
    R = bench_run()
    coop = workload == COOP_CELL
    res = copy.deepcopy(R.resolve(
        R.load_spec(), "metro1024-steady-demsa" if coop else workload))
    if coop:
        res["traffic"].update(COOP_TRAFFIC)
        res["cell"]["name"] = COOP_CELL
    res["cfg"]["n_edges"] = n_edges
    res["cell"]["chips"] = chips
    if "horizon_ms" in res["traffic"]:
        res["traffic"]["horizon_ms"] = horizon_ms
    return R.Cell(res, seed, seconds, trace, say=lambda m: None)


def run(cell) -> dict:
    return bench_run().run_cell(cell, FAKE_DEVICE)


# -- the faults a cell's timed path can have, planted in the program ------

def _faults():
    import jax
    import jax.numpy as jnp
    from repro.sim import fleet_jax as fj

    real_step = fj.FleetProgram.step_chunk

    def copy(tree):
        return jax.tree.map(jnp.copy, tree)

    def unchanged(self, prof, pp, state, signals):
        """The step returns the state it was given."""
        _, res = real_step(self, prof, pp, copy(state), signals)
        return state, res

    def half_left_out(self, prof, pp, state, signals):
        """Only the first half of the edges is stepped."""
        new, res = real_step(self, prof, pp, copy(state), signals)
        n = state.busy_rem.shape[0]
        keep = jnp.arange(n) < n // 2

        def pick(a, b):
            return jnp.where(keep.reshape((n,) + (1,) * (a.ndim - 1)), a, b)
        return jax.tree.map(pick, new, state), res

    def answer_altered(self, prof, pp, state, signals):
        """One outcome counter is off by one where it is produced."""
        new, res = real_step(self, prof, pp, state, signals)
        return new._replace(n_success=new.n_success.at[0, 0].add(1)), res

    return {"unchanged": unchanged, "half_left_out": half_left_out,
            "answer_altered": answer_altered}


FAULT_NAMES = ("answer_altered", "half_left_out", "unchanged")


def fault(name: str):
    return _faults()[name]
