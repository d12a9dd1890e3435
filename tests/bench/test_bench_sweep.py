"""The sweep entry at a tiny size on the CPU.

A sound run is correct with every check at 0, the bfloat16 control
(``bench/control.py``) fails the configuration's limits, and watching
the sweep's calls into the program (the host clock round its phases,
the last pass's end states kept) leaves its rows as they are.  The
missions are cut to 56 s in the tests only, so that the faults fall
inside them: ``flash-crowd``'s burst (from 30 s) and flood (from
40 s) over two edges, which exchange tasks under DEMS-A-COOP;
``partition``'s WAN partition (from 40 s) and edge crash (from 50 s) over
two edges; ``cloud-crunch``'s burst on one edge with two FaaS slots.
"""
from __future__ import annotations

import copy
import importlib.util

import jax
import pytest

from benchkit import ROOT, SEED, bench_run, run

CELL = "registry-sweep-warm"
TINY = dict(scenarios=["flash-crowd", "partition", "cloud-crunch"],
            duration_ms=56_000.0, seeds=1)


def sweep_cell(seconds: float = 0.3, trace: bool = False):
    """The sweep cell with its missions cut short."""
    R = bench_run()
    res = copy.deepcopy(R.resolve(R.load_spec(), CELL))
    res["traffic"].update(TINY)
    return R.Cell(res, SEED, seconds, trace, say=lambda m: None)


@pytest.fixture(scope="module")
def sound():
    c = sweep_cell()
    return c, run(c)


def test_sound_sweep_is_correct(sound):
    c, out = sound
    assert out["correct"], c.checks
    assert set(c.checks) == {"ledger_gap", "mismatch_pct", "row_gap"}
    assert all(v["value"] == 0 for v in c.checks.values()), c.checks
    assert out["failed"] == 0
    assert out["attempted"] == c.layer["passes"] * 6        # 3 x 2 runs
    assert set(out["metrics"]) == {"sweep_s", "setup_s"}


def test_sound_sweep_exchanges_and_times_its_phases(sound):
    c, _ = sound
    # the five silo lanes share a bucket, the two-edge COOP runs another,
    # the one-edge COOP run a third
    assert c.layer["buckets"] == 3 and c.layer["lanes"] == 8
    assert c.layer["ticks"] == c.layer["passes"] * 3 * 2240
    shares = {}
    for name in ("sweep_lower_pct", "sweep_bucket_pct"):
        shares[name] = bench_run().load_reader(
            ROOT / "bench" / "metrics" / f"{name}.py").read(
                dict(layer=c.layer))
        assert 0 < shares[name] < 100
    assert sum(shares.values()) <= 100


def test_the_cut_flash_crowd_exchanges_tasks():
    """The exchange between edges runs in the tiny sweep, so leaving it
    out (``test_bench_faults_sweep.py``) has something to change."""
    from repro.scenarios.runner import run_registry_sweep

    rows = run_registry_sweep(["flash-crowd"], ("DEMS-A-COOP",),
                              (SEED % 2**31,),
                              duration_ms=TINY["duration_ms"])
    assert rows[0]["peer_offloaded"] > 0


def test_control_fails_the_sweep_limits():
    spec = importlib.util.spec_from_file_location(
        "bench_control", ROOT / "bench" / "control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    _, cfg, traffic = control.load(CELL)
    traffic.update(TINY)
    got = control.sweep_readings(cfg, traffic, SEED,
                                 int(TINY["duration_ms"]
                                     / cfg["scheduler"]["dt_ms"]))
    limits = cfg["limits"]
    assert any(got[k] > limits[k] for k in got), got


def test_watched_sweep_leaves_the_rows_as_they_are(tmp_path):
    """Under the profiler, the watched sweep leaves its spans round the
    lowering and the buckets in the trace, keeps each bucket's end state,
    and returns rows bitwise equal to those of a plain call."""
    from harness import sweep, trace as T
    from repro.scenarios.runner import run_registry_sweep

    # one scenario and a 2 s mission: the profiler records every
    # operation the CPU runs
    c = sweep_cell(trace=True)
    c.traffic.update(scenarios=["cloud-crunch"], duration_ms=2000.0)
    args = sweep.plan(c.traffic, c.seed)
    kw = dict(dt=c.cfg["scheduler"]["dt_ms"], duration_ms=args[3])
    plain = run_registry_sweep(*args[:3], **kw)
    w = bench_run().Window(c)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with w.span("bench.window"), sweep.watched(w.span) as seen:
            timed = run_registry_sweep(*args[:3], **kw)
    finally:
        jax.profiler.stop_trace()
    assert timed == plain
    assert seen.spent["lower"] > 0 and seen.spent["bucket"] > 0
    assert len(seen.buckets()) == 2
    names = [h[0] for h in T.load(str(tmp_path)).host]
    assert names.count("bench.sweep.lower") == 1
    assert names.count("bench.sweep.bucket") == 2


def test_traced_sweep_is_correct_and_reads_its_layers():
    """A traced run: the profile ends early in the first bucket, the run
    is correct as untraced, and both shares are read."""
    c = sweep_cell(trace=True)
    out = run(c)
    assert out["correct"], c.checks
    assert set(out["metrics"]) == {"setup_compile_s", "sweep_lower_pct",
                                   "sweep_bucket_pct"}
    shares = [out["metrics"][m]["value"]
              for m in ("sweep_lower_pct", "sweep_bucket_pct")]
    assert all(0 < v < 100 for v in shares) and sum(shares) <= 100
    # the traced part: the lowering and the start of the first bucket
    assert 0 < out["device"]["window_s"] < c.layer["window_s"]
