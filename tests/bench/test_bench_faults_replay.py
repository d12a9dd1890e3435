"""A run whose timed path is broken underneath comes out not correct:
the replay cells.

Each test drives the rest of a run at a tiny size on the CPU (only the
harness's look for a chip is skipped) with the program's tick step
broken in one way the cell can have: the state returned unchanged, half
of the edges left out, one answer altered where it is produced, and
with cooperation (on the tests' COOP mix, ``benchkit.COOP_CELL``) the
exchange between edges left out.  The sound run is correct.
"""
from __future__ import annotations

import pytest

from benchkit import COOP_CELL, FAULT_NAMES, fault, run, tiny_cell

from repro.sim import fleet_jax as fj
from repro.obs.prof import reset_fleet_programs

CELLS = ["metro1024-steady-demsa", COOP_CELL]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_replay_is_correct(cell):
    c = tiny_cell(cell, n_edges=8, seconds=0.4)
    out = run(c)
    assert out["correct"], c.checks
    assert all(v["value"] == 0 for v in c.checks.values()), c.checks


@pytest.mark.parametrize("name", FAULT_NAMES)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_replay_step_is_not_correct(cell, name, monkeypatch):
    monkeypatch.setattr(fj.FleetProgram, "step_chunk", fault(name))
    c = tiny_cell(cell, n_edges=8, seconds=0.4)
    assert not run(c)["correct"], (name, c.checks)


def test_exchange_left_out_is_not_correct(monkeypatch):
    monkeypatch.setattr(fj, "peer_offload", lambda fs, *a, **k: fs)
    reset_fleet_programs()
    try:
        c = tiny_cell(COOP_CELL, n_edges=16, seconds=0.6)
        out = run(c)
    finally:
        monkeypatch.undo()
        reset_fleet_programs()
    assert not out["correct"], c.checks
    assert c.checks["ledger_gap"]["value"] == 0   # accounting stays exact
