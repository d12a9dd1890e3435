"""What decides ``correct``: the program's results against the reference.

Three numbers, each with a limit from the configuration file's
``limits`` (see PERF.md for the readings each limit was set from):

* ``ledger_gap``: the largest break, over edges, of the exact outcome
  accounting the configuration guarantees: tasks that arrived (counted
  from the traffic the program was fed) plus peer imports, against
  settled (hit, miss, drop) plus in flight plus peer exports, read from
  the program's end state;
* ``mismatch_pct``: integer end state per edge (outcome counters per
  model, peer transfers, queue occupancy) against the plain reference
  run over the same inputs, as the sum of absolute differences over the
  tasks that arrived on the compared edges, in percent;
* ``record_mismatch_pct`` (live entry only): the share of decided ticks
  whose fleet-summed decision record differs from the reference's.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

from harness import ref_fleet

REFS = pathlib.Path(__file__).resolve().parents[1] / "refs"

FIELDS = ("n_success", "n_miss", "n_drop", "n_stolen", "n_edge_exec",
          "n_peer_out", "n_peer_in", "eq_depth", "cq_depth")
RECORD_FIELDS = ref_fleet.COUNTERS + ref_fleet.OUTCOMES
_EDGE_AXIS = ("theta", "bw", "arrive", "order", "load_mult", "valid",
              "exec_jit", "edge_up", "link_up")


def reference(cfg: dict, policy: str, n_edges: int, dtype=np.float32,
              **lanes):
    """The policy's plain reference, from its own file
    ``bench/refs/<policy>.py``: ``make(cfg, n_edges, dtype)`` returns an
    object with ``step(inputs) -> record`` and ``outcome()``.  ``lanes``
    (``slots``, ``groups``: per edge) stack independent runs, and are
    passed on only where given."""
    path = REFS / f"{policy}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no plain reference for policy {policy!r} "
                                f"({path.name} under bench/refs/)")
    spec = importlib.util.spec_from_file_location(
        "bench_ref_" + policy.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make(cfg, n_edges, dtype, **lanes)


def ref_replay(cfg: dict, policy: str, segments: list[dict],
               dtype=np.float32):
    """Step the reference over every tick the program stepped, on every
    edge; returns ``(outcome, arrived per edge)``."""
    ref = reference(cfg, policy, segments[0]["arrive"].shape[1], dtype)
    arrived = 0
    for seg in segments:
        seg = {k: np.asarray(v) for k, v in seg.items()}
        for t in range(seg["times"].shape[0]):
            x = {k: v[t] for k, v in seg.items()}
            x["now"] = x.pop("times")
            arrived = arrived + x["arrive"].sum(-1)
            ref.step(x)
    return ref.outcome(), arrived


def live_order(order_seed: int, tick: int, n_edges: int,
               n_models: int) -> np.ndarray:
    """The live controller's insertion order for one tick: its documented
    per-tick seeded permutation ``[order_seed, 0x0dde, tick]``."""
    return np.random.default_rng([order_seed, 0x0dde, tick]).permuted(
        np.tile(np.arange(n_models), (n_edges, 1)), axis=1).astype(np.int32)


def ref_live(cfg: dict, policy: str, arrivals: list[list[tuple[int, int]]],
             order_seed: int, n_ticks: int, dtype=np.float32):
    """Step the reference over ``n_ticks`` live ticks; ``arrivals[t]`` holds
    the ``(edge, model)`` submissions of tick ``t``.  Returns
    ``(records, outcome, arrived per edge)``."""
    n_edges, n_models = cfg["n_edges"], len(cfg["models"])
    s = cfg["scheduler"]
    ref = reference(cfg, policy, n_edges, dtype)
    arrived = np.zeros(n_edges, np.int64)
    records = []
    for t in range(n_ticks):
        arrive = np.zeros((n_edges, n_models), bool)
        for e, m in arrivals[t]:
            arrive[e, m] = True
        arrived += arrive.sum(-1)
        records.append(ref.step(dict(
            now=np.float32(t * s["dt_ms"]),
            theta=np.zeros(n_edges, np.float32),
            bw=np.full(n_edges, s["nominal_bw_mbps"], np.float32),
            arrive=arrive, order=live_order(order_seed, t, n_edges, n_models),
            load_mult=np.ones(n_edges, np.float32), cloud_up=True,
            valid=np.ones(n_edges, bool),
            exec_jit=np.ones((n_edges, n_models, 2), np.float32),
            edge_up=np.ones(n_edges, bool), link_up=np.ones(n_edges, bool))))
    return records, ref.outcome(), arrived


def ledger_gap(got: dict, arrived: np.ndarray) -> int:
    settled = (got["n_success"].sum(-1) + got["n_miss"].sum(-1)
               + got["n_drop"].sum(-1))
    held = got["eq_depth"] + got["cq_depth"]
    gap = (arrived + got["n_peer_in"]) - (settled + held + got["n_peer_out"])
    return int(np.abs(gap).max(initial=0))


def mismatch_count(got: dict, want: dict) -> int:
    """Sum of absolute differences over the compared integer fields."""
    return sum(int(np.abs(np.asarray(got[f], np.int64)
                          - np.asarray(want[f], np.int64)).sum())
               for f in FIELDS)


def mismatch_pct(got: dict, want: dict, arrived: np.ndarray) -> float:
    return 100.0 * mismatch_count(got, want) / max(int(arrived.sum()), 1)


def record_mismatch_pct(got: list[dict], want: list[dict]) -> float:
    """Share of ticks whose decision record differs; a tick the program
    did not decide counts as differing."""
    bad = sum(1 for i, w in enumerate(want)
              if i >= len(got) or any(int(got[i][f]) != int(w[f])
                                      for f in RECORD_FIELDS))
    return 100.0 * bad / max(len(want), 1)
