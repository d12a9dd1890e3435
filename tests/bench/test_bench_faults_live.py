"""A run whose timed path is broken underneath comes out not correct:
the live cell.

Each test drives the rest of a run at a tiny size on the CPU (only the
harness's look for a chip is skipped) with the program's tick step
broken in one way the cell can have: the state returned unchanged, half
of the edges left out, one answer altered where it is produced.  The
sound run is correct.
"""
from __future__ import annotations

import pytest

from benchkit import FAULT_NAMES, fault, run, tiny_cell

from repro.sim import fleet_jax as fj

CELL = "vip27-live-demsa"


def test_sound_live_run_is_correct():
    c = tiny_cell(CELL, n_edges=27, seconds=0.4)
    out = run(c)
    assert out["correct"], c.checks
    assert all(v["value"] == 0 for v in c.checks.values()), c.checks


@pytest.mark.parametrize("name", FAULT_NAMES)
def test_broken_live_step_is_not_correct(name, monkeypatch):
    monkeypatch.setattr(fj.FleetProgram, "step_chunk", fault(name))
    c = tiny_cell(CELL, n_edges=27, seconds=0.4)
    assert not run(c)["correct"], (name, c.checks)
