"""The traffic generator's counts at a tiny size match its parameters."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from benchkit import SEED, traffic

from harness import gen

DT = 25.0
N_MODELS = 6


def signals(t: dict, n_edges: int, seed: int = SEED):
    return jax.device_get(gen.replay_signals(gen.seed_key(seed), t, n_edges,
                                             N_MODELS, DT))


@pytest.mark.parametrize("name,drones", [("steady-3da", 3),
                                         ("hotspot-4da-2da", None)])
def test_tasks_per_drone_per_second(name, drones):
    """Each drone brings one segment a second, every model once per
    segment; no two drones of an edge share a tick, so none is lost."""
    t = traffic(name, horizon_ms=10_000.0)
    n_edges = 16
    sig = signals(t, n_edges)
    per_edge = sig["arrive"].sum(axis=(0, 2))
    seconds = t["horizon_ms"] / 1e3
    d_e, _ = gen.fleet_draw(gen.seed_key(SEED), t, n_edges, DT)
    if drones is not None:
        assert (d_e == drones).all()
    np.testing.assert_array_equal(per_edge, d_e * N_MODELS * seconds)
    # a segment brings every model, each exactly once
    seg = sig["arrive"].any(-1)
    assert (sig["arrive"].sum(-1)[seg] == N_MODELS).all()
    # the insertion order is a permutation of the models in every cell
    np.testing.assert_array_equal(np.sort(sig["order"], -1),
                                  np.broadcast_to(np.arange(N_MODELS),
                                                  sig["order"].shape))


def test_hot_edges_are_one_eighth():
    t = traffic("hotspot-4da-2da")
    for seed in (1, SEED):
        drones, _ = gen.fleet_draw(gen.seed_key(seed), t, 1024, DT)
        assert (drones == 4).sum() == 128
        assert (drones == 2).sum() == 896
        assert drones.sum() == 2304


def test_seed_fixes_the_traffic():
    t = traffic("steady-3da", horizon_ms=2000.0)
    a, b = signals(t, 8, seed=5), signals(t, 8, seed=5)
    c = signals(t, 8, seed=6)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["arrive"], c["arrive"])


def test_window_wraps_at_the_horizon():
    t = traffic("steady-3da", horizon_ms=2000.0)      # 80 ticks
    sig = jax.device_put(signals(t, 4))
    take = gen.window_fn(32, 80, DT)
    win = jax.device_get(take(sig, np.int32(64)))     # ticks 64..95
    idx = np.arange(64, 96) % 80
    np.testing.assert_array_equal(win["arrive"], np.asarray(
        sig["arrive"])[idx])
    np.testing.assert_array_equal(win["times"], np.arange(64, 96) * DT)


def test_live_schedule_counts():
    t = traffic("live-3da")
    drones, phases = gen.fleet_draw(gen.seed_key(SEED), t, 27, DT)
    period = gen.period_ticks(t, DT)
    by_tick = gen.live_schedule(drones, phases, period, N_MODELS)
    # 27 VIPs x 3 drones x 6 models = 486 tasks a second, none shared
    assert sum(len(x) for x in by_tick) == 486
    for tick in by_tick:
        assert len(set(tick)) == len(tick)
