"""Mesh construction: the one Auto-axis constructor every mesh goes
through, and the production (16×16, 2×16×16) and host meshes built on it.

A function, not a module-level constant, so importing this module never
touches jax device state (device count is locked at first jax init —
``dryrun.py`` must set ``XLA_FLAGS`` before importing anything jax).
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link


def make_mesh(shape: tuple[int, ...],
              axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """The repo's one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to Explicit axes, under which the fleet
    tick's vmap over a sharded edge axis is refused; with Auto axes the
    partitioner places the collectives.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this process actually has (tests / CPU smoke)."""
    return make_mesh((jax.device_count(), 1), ("data", "model"))
