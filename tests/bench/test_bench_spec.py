"""BENCHMARK.json and the files it names: every cell resolves by name, and
new traffic and metric files are picked up without editing a file."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from benchkit import ROOT, bench_run

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")


def test_names_units_and_keys():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    """Configuration, traffic and every per-layer reader exist, and each
    per-layer metric's cell reports the end-to-end metric it moves."""
    import importlib

    R = bench_run()
    res = R.resolve(SPEC, cell)
    assert res["cfg"]["name"] == res["cell"]["config"]
    entry = res["traffic"]["entry"]
    assert (ROOT / "bench" / "harness" / f"{entry}.py").is_file()
    assert callable(importlib.import_module(f"harness.{entry}").run)
    e2e = {m["name"] for m in res["e2e"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert res["layers"], "every cell reports a per-layer metric"
    for m in res["layers"]:
        assert m["moves"] in e2e
        assert callable(R.load_reader(res["readers"][m["name"]]).read)
    listed = [m for m in SPEC["per_layer"] if cell in m.get("workloads", [])]
    assert {m["name"] for m in listed} <= {m["name"] for m in res["layers"]}


def test_config_files_are_distinct_and_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert set(cfg["limits"]) >= {"ledger_gap", "mismatch_pct"}
        assert all(v is not None for v in cfg["limits"].values())


def test_new_traffic_and_metric_files_are_picked_up(tmp_path):
    """A later change adds a mix and a metric as new files and entries;
    no file that is already there changes."""
    for d in ("bench",):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    traffic = json.loads((ROOT / "bench/traffic/steady-3da.json").read_text())
    traffic["drones_per_edge"] = 1
    (tmp_path / "bench/traffic/sparse-1da.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/calls_per_window.py").write_text(
        "def read(ctx):\n    return ctx['layer'].get('calls')\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(
        name="metro1024-sparse-demsa", config="metro-1024",
        traffic="sparse-1da", chips=1, why="one drone per edge"))
    for m in spec["end_to_end"]:
        if m["name"] == "edge_ticks_per_s":
            m["workloads"].append("metro1024-sparse-demsa")
    spec["per_layer"].append(dict(
        name="calls_per_window", unit="calls", better="higher",
        source="host_clock", layer="tick program", moves="edge_ticks_per_s",
        workloads=["metro1024-sparse-demsa"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    R = bench_run()
    res = R.resolve(spec, "metro1024-sparse-demsa", root=tmp_path)
    assert res["traffic"]["drones_per_edge"] == 1
    names = [m["name"] for m in res["layers"]]
    assert "calls_per_window" in names
    reader = R.load_reader(res["readers"]["calls_per_window"])
    assert reader.read(dict(layer={"calls": 7})) == 7
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("cell", CELLS)
def test_cell_policy_has_a_reference_file(cell):
    """Each of a cell's policies finds its plain reference by name."""
    from harness import check

    res = bench_run().resolve(SPEC, cell)
    t = res["traffic"]
    policies = t["policies"] if "policies" in t else [t["policy"]]
    assert policies
    for policy in policies:
        ref = check.reference(res["cfg"], policy, 2)
        assert callable(ref.step) and callable(ref.outcome)


def test_unknown_policy_has_no_reference():
    from harness import check

    cfg = json.loads((ROOT / "bench/configs/metro-1024.json").read_text())
    with pytest.raises(FileNotFoundError, match="GEMS-A"):
        check.reference(cfg, "GEMS-A", 2)


def test_new_reference_file_is_picked_up(tmp_path, monkeypatch):
    """A later cell on another policy adds ``bench/refs/<policy>.py`` and
    edits no file."""
    from harness import check

    (tmp_path / "NEW-POLICY.py").write_text(
        "class Ref:\n"
        "    def __init__(self, n):\n        self.n = n\n"
        "    def step(self, x):\n        return {}\n"
        "    def outcome(self):\n        return {'n': self.n}\n\n"
        "def make(cfg, n_edges, dtype):\n    return Ref(n_edges)\n")
    monkeypatch.setattr(check, "REFS", tmp_path)
    assert check.reference({}, "NEW-POLICY", 5).outcome() == {"n": 5}
