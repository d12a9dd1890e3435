"""``bench/run.py`` refuses to run, with no result line, off the chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

from benchkit import ROOT, bench_run


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "metro1024-steady-demsa", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def _fake_devices(kind: str, n: int):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind, id=i)
            for i in range(n)]


def test_exits_nonzero_for_a_device_missing_from_the_peaks_table(
        monkeypatch, capsys):
    R = bench_run()
    monkeypatch.setattr(R.jax, "devices",
                        lambda *a: _fake_devices("TPU v99", 1))
    rc = R.main(["--workload", "metro1024-steady-demsa", "--seed", "3",
                 "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "peaks.json" in out.err


def test_exits_nonzero_with_fewer_chips_than_the_cell_asks(monkeypatch,
                                                           capsys):
    R = bench_run()
    spec = json.loads(json.dumps(R.load_spec()))
    for w in spec["workloads"]:
        w["chips"] = 4
    monkeypatch.setattr(R, "load_spec", lambda: spec)
    monkeypatch.setattr(R.jax, "devices",
                        lambda *a: _fake_devices("TPU v5 lite", 1))
    rc = R.main(["--workload", "metro1024-steady-demsa", "--seed", "3",
                 "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "4 chips" in out.err


def test_known_device_passes_the_look(monkeypatch):
    R = bench_run()
    monkeypatch.setattr(R.jax, "devices",
                        lambda *a: _fake_devices("TPU v5 lite", 4))
    assert R.device_info(4, {"TPU v5 lite": {}}) == dict(
        platform="tpu", kind="TPU v5 lite", count=4)
