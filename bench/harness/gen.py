"""The one traffic generator: a traffic file's parameters to arrivals.

Every traffic mix is a JSON file under ``bench/traffic/`` that this module
reads; a new mix is a new file, not new code.  Drones fly one video
segment per ``segment_period_ms`` at a random phase and each segment
brings one task per model of the configuration.  Phases are distinct
ticks within the period on each edge, so two drones of one edge never
land in the same tick and every arrival is kept: an edge with ``d``
drones sees exactly ``d`` segments per period.

* ``drones_per_edge``: drones on an ordinary edge;
* ``hot_fraction``, ``hot_drones_per_edge``: the share of edges, drawn
  uniformly from the seed, that carry the heavier load;
* ``horizon_ms``: the mission a replay's signals cover (the window wraps
  past it, shifting time by the horizon);
* ``theta_ms``, ``bw_mbps``: added WAN latency and cellular bandwidth,
  held constant.

Replay signals for the whole horizon are built on the device in one
jitted call from the seed; the live mix uses the same draw of phases.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, high bits folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def horizon_ticks(traffic: dict, dt: float) -> int:
    return int(round(traffic["horizon_ms"] / dt))


def period_ticks(traffic: dict, dt: float) -> int:
    p = traffic["segment_period_ms"] / dt
    if p != int(p):
        raise ValueError("segment_period_ms must be a whole number of ticks")
    return int(p)


def hot_count(traffic: dict, n_edges: int) -> int:
    return int(round(n_edges * traffic.get("hot_fraction", 0.0)))


def _fleet_draw(key, n_edges: int, period: int, base: int, hot_n: int,
                hot_d: int):
    """Per-edge drone counts and distinct phase ticks (``[E, D]``)."""
    k_hot, k_phase = jax.random.split(key)
    dmax = max(base, hot_d)
    hot_rank = jnp.argsort(jax.random.uniform(k_hot, (n_edges,)))
    hot = jnp.zeros(n_edges, bool).at[hot_rank[:hot_n]].set(True)
    drones = jnp.where(hot, hot_d, base).astype(jnp.int32)
    phases = jnp.argsort(jax.random.uniform(k_phase, (n_edges, period)),
                         axis=-1)[:, :dmax].astype(jnp.int32)
    return drones, phases


def fleet_draw(key, traffic: dict, n_edges: int, dt: float):
    """Host copy of the draw: ``(drones [E], phases [E, D])``, the same
    draw :func:`replay_signals` makes from ``key``."""
    key = jax.random.fold_in(key, 1)
    fn = jax.jit(functools.partial(
        _fleet_draw, n_edges=n_edges, period=period_ticks(traffic, dt),
        base=traffic["drones_per_edge"], hot_n=hot_count(traffic, n_edges),
        hot_d=traffic.get("hot_drones_per_edge", 0)))
    return tuple(np.asarray(a) for a in jax.device_get(fn(key)))


def replay_signals(key, traffic: dict, n_edges: int, n_models: int,
                   dt: float):
    """The whole mission's dense tick signals, on the device.

    Returns a dict keyed by the fleet model's signal names (``times``,
    ``theta``, ``bw``, ``arrive``, ``order``, ``load_mult``, ``cloud_up``,
    ``valid``, ``exec_jit``, ``edge_up``, ``link_up``); ``order`` is a
    fresh random permutation of the models per (tick, edge).
    """
    t_n = horizon_ticks(traffic, dt)
    period = period_ticks(traffic, dt)
    base = traffic["drones_per_edge"]
    hot_n = hot_count(traffic, n_edges)
    hot_d = traffic.get("hot_drones_per_edge", 0)
    theta, bw = float(traffic["theta_ms"]), float(traffic["bw_mbps"])

    def build(key):
        k_fleet, k_order = jax.random.fold_in(key, 1), jax.random.fold_in(
            key, 2)
        drones, phases = _fleet_draw(k_fleet, n_edges, period, base, hot_n,
                                     hot_d)
        tick = jnp.arange(t_n, dtype=jnp.int32)
        live = jnp.arange(phases.shape[1]) < drones[:, None]      # [E, D]
        seg = ((tick[:, None, None] % period == phases[None])
               & live[None]).any(-1)                              # [T, E]
        e_shape = (t_n, n_edges)
        return dict(
            times=tick.astype(jnp.float32) * jnp.float32(dt),
            theta=jnp.full(e_shape, theta, jnp.float32),
            bw=jnp.full(e_shape, bw, jnp.float32),
            arrive=jnp.broadcast_to(seg[..., None], e_shape + (n_models,)),
            order=jnp.argsort(jax.random.uniform(
                k_order, e_shape + (n_models,)), axis=-1).astype(jnp.int32),
            load_mult=jnp.ones(e_shape, jnp.float32),
            cloud_up=jnp.ones(t_n, bool),
            valid=jnp.ones(e_shape, bool),
            exec_jit=jnp.ones(e_shape + (n_models, 2), jnp.float32),
            edge_up=jnp.ones(e_shape, bool),
            link_up=jnp.ones(e_shape, bool))

    return jax.jit(build)(key)


def window_fn(n_ticks: int, horizon: int, dt: float):
    """Jitted ``(signals, start) -> ticks [start, start + n_ticks)``.

    Ticks past the horizon wrap to its start with time shifted by the
    horizon, so a replay can run on for as long as the window lasts.
    """
    span = jnp.float32(horizon * dt)

    def take(sig, start):
        t = start + jnp.arange(n_ticks, dtype=jnp.int32)
        idx, lap = t % horizon, t // horizon
        out = {k: v[idx] for k, v in sig.items()}
        out["times"] = out["times"] + lap.astype(jnp.float32) * span
        return out

    return jax.jit(take)


def live_schedule(drones: np.ndarray, phases: np.ndarray, period: int,
                  n_models: int):
    """Per tick-of-period the ``(edge, model)`` arrivals of the live mix."""
    by_tick: list[list[tuple[int, int]]] = [[] for _ in range(period)]
    for e in range(len(drones)):
        for d in range(int(drones[e])):
            for m in range(n_models):
                by_tick[int(phases[e, d])].append((e, m))
    return by_tick
