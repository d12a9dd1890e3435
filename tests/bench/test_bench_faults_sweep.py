"""A run whose timed path is broken underneath comes out not correct:
the sweep cell.

Each test drives the rest of a run at the tiny size of
``test_bench_sweep.py`` on the CPU (only the harness's look for a chip is
skipped) with the program broken in one way a sweep can have: the tick
step returning its state unchanged, half of a bucket's lanes left out,
one answer altered where the step produces it, the exchange between a
run's edges left out, and a row's total altered where the sweep
summarises it.
"""
from __future__ import annotations

import pytest

from benchkit import FAULT_NAMES, fault, run
from test_bench_sweep import sweep_cell

from repro.obs.prof import reset_fleet_programs
from repro.scenarios import runner
from repro.sim import fleet_jax as fj


@pytest.mark.parametrize("name", FAULT_NAMES)
def test_broken_sweep_step_is_not_correct(name, monkeypatch):
    monkeypatch.setattr(fj.FleetProgram, "step_chunk", fault(name))
    c = sweep_cell()
    assert not run(c)["correct"], (name, c.checks)


def test_exchange_left_out_is_not_correct(monkeypatch):
    monkeypatch.setattr(fj, "peer_offload", lambda fs, *a, **k: fs)
    reset_fleet_programs()
    try:
        c = sweep_cell()
        out = run(c)
    finally:
        monkeypatch.undo()
        reset_fleet_programs()
    assert not out["correct"], c.checks
    assert c.checks["ledger_gap"]["value"] == 0   # accounting stays exact


def test_row_altered_where_summarised_is_not_correct(monkeypatch):
    real = runner.fleet_summary

    def altered(final):
        row = real(final)
        return dict(row, completed=row["completed"] + 1)

    monkeypatch.setattr(runner, "fleet_summary", altered)
    c = sweep_cell()
    out = run(c)
    assert not out["correct"], c.checks
    assert c.checks["row_gap"]["value"] == 1
    assert c.checks["mismatch_pct"]["value"] == 0
