"""The four-chip sweep entry (``harness/sweep_mesh.py``) at a tiny size.

The cell's runs go through ``benchkit`` in one child process on the CPU
only (``JAX_PLATFORMS=cpu``, four forced host devices; a test process may
hold an accelerator, and a child that reached for it would fail or
hang).  One scenario with 4 seeds and 8 s missions, so that every
bucket spans the four devices: a sound run is correct with every check
at 0, a traced one reads the cell's five per-layer metrics, and the
program broken where only a mesh can break it comes out not correct:
one device's shard of each bucket's replicas returned unchanged by the
step, and one lane left out.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from benchkit import ROOT, bench_run

CELL = "registry-sweep-x4"
READERS = ("sweep_lower_pct", "sweep_bucket_pct", "sweep_gather_pct",
           "sweep_bucket_tick_ms")

_CHILD = textwrap.dedent("""
    import copy, json, sys
    sys.path.insert(0, "tests/bench")
    from benchkit import SEED, bench_run, run
    import jax
    import jax.numpy as jnp
    from repro.sim import fleet_jax as fj

    R = bench_run()
    real = fj.FleetProgram.step_chunk

    def cell(trace=False):
        res = copy.deepcopy(R.resolve(R.load_spec(), %(cell)r))
        res["traffic"].update(scenarios=["cloud-crunch"],
                              duration_ms=8000.0)
        return R.Cell(res, SEED, 0.3, trace, say=lambda m: None)

    def kept_where(keep_of):
        def step(self, prof, pp, state, signals):
            new, res = real(self, prof, pp, jax.tree.map(jnp.copy, state),
                            signals)
            keep = keep_of(state.busy_rem)

            def pick(a, b):
                return jnp.where(
                    keep.reshape(keep.shape + (1,) * (a.ndim - 1)), b, a)
            return jax.tree.map(pick, new, state), res
        return step

    def first_shard(a):
        # the replicas on the first device of the bucket's mesh
        shard = min(a.addressable_shards, key=lambda s: s.device.id)
        return jnp.zeros(a.shape[0], bool).at[shard.index[0]].set(True)

    FAULTS = dict(
        shard_unchanged=first_shard,
        lane_left_out=lambda a: jnp.arange(a.shape[0]) == a.shape[0] - 1)

    out = {"devices": jax.device_count()}
    for name, trace in (("sound", False), ("traced", True)):
        c = cell(trace)
        got = run(c)
        out[name] = dict(correct=got["correct"], checks=c.checks,
                         failed=got["failed"], attempted=got["attempted"],
                         metrics=sorted(got["metrics"]), layer=c.layer,
                         device=got["device"])
    for name, keep_of in FAULTS.items():
        fj.FleetProgram.step_chunk = kept_where(keep_of)
        c = cell()
        got = run(c)
        fj.FleetProgram.step_chunk = real
        out[name] = dict(correct=got["correct"], checks=c.checks)
    print("SWEEP-MESH-CELL " + json.dumps(out))
""" % dict(cell=CELL))


@pytest.fixture(scope="module")
def child():
    # four host devices, whatever count an earlier import in this
    # worker put into XLA_FLAGS
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=" ".join(
                   flags + ["--xla_force_host_platform_device_count=4"]),
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SWEEP-MESH-CELL ")][-1]
    out = json.loads(line.split(" ", 1)[1])
    assert out["devices"] == 4
    return out


def _read(name: str, layer: dict):
    return bench_run().load_reader(
        ROOT / "bench" / "metrics" / f"{name}.py").read(dict(layer=layer))


def test_sound_x4_sweep_is_correct(child):
    got = child["sound"]
    assert got["correct"], got["checks"]
    assert set(got["checks"]) == {"ledger_gap", "mismatch_pct", "row_gap"}
    assert all(v["value"] == 0 for v in got["checks"].values())
    assert got["failed"] == 0
    assert got["attempted"] == got["layer"]["passes"] * 8     # 2 x 4
    assert got["metrics"] == ["setup_s", "sweep_s"]


def test_sound_x4_sweep_reads_its_layers(child):
    layer = child["sound"]["layer"]
    # the four silo lanes, the four COOP runs
    assert layer["buckets"] == 2 and layer["lanes"] == 8
    # untraced, every bucket counts
    assert layer["sweep_clean_bucket_ticks"] == layer["ticks"] == \
        layer["passes"] * 2 * 320
    assert layer["sweep_clean_bucket_s"] == layer["sweep_bucket_s"]
    got = {name: _read(name, layer) for name in READERS}
    shares = [got[n] for n in READERS if n.endswith("_pct")]
    assert all(0 < v < 100 for v in shares) and sum(shares) <= 100
    assert got["sweep_bucket_tick_ms"] == pytest.approx(
        1e3 * layer["sweep_bucket_s"] / layer["ticks"])


def test_traced_x4_sweep_is_correct_and_reads_its_layers(child):
    """The profile ends in the window's first bucket, whose time holds
    the profiler's stop: the time a bucket-tick takes leaves it out."""
    got = child["traced"]
    assert got["correct"], got["checks"]
    assert got["metrics"] == sorted(READERS + ("setup_compile_s",))
    assert 0 < got["device"]["window_s"] < got["layer"]["window_s"]
    layer = got["layer"]
    assert layer["sweep_clean_bucket_ticks"] == layer["ticks"] - 320
    assert 0 < layer["sweep_clean_bucket_s"] < layer["sweep_bucket_s"]


@pytest.mark.parametrize("fault", ["shard_unchanged", "lane_left_out"])
def test_broken_x4_sweep_is_not_correct(child, fault):
    got = child[fault]
    assert not got["correct"], got["checks"]
    assert got["checks"]["mismatch_pct"]["value"] > \
        got["checks"]["mismatch_pct"]["limit"]


def test_new_readers_find_nothing_in_another_entry():
    """The one-chip sweep's layer has no record to read: the new
    metrics are left out there, not read as 0."""
    layer = dict(window_s=100.0, sweep_lower_s=10.0, sweep_bucket_s=90.0,
                 ticks=84_000)
    assert _read("sweep_gather_pct", layer) is None
    assert _read("sweep_bucket_tick_ms", layer) is None


@pytest.mark.parametrize("chips,ok", [((4, 4), True), ((4, 2), False),
                                      ((1, 4), False)])
def test_buckets_must_fan_over_the_chips(chips, ok):
    """A bucket that ran on fewer (or more) chips than the largest count
    of the cell's chips dividing its lanes stops the run in set-up."""
    from types import SimpleNamespace

    from harness import sweep_mesh
    from repro.obs.prof import SweepBucket, SweepStats

    stats = SweepStats(buckets=[SweepBucket(lanes, c, 80, 1.0, 0.1)
                                for lanes, c in zip((76, 20), chips)])
    cell = SimpleNamespace(chips=4)
    if ok:
        sweep_mesh._require_fanned_out(cell, stats)
    else:
        with pytest.raises(ValueError, match="fan over"):
            sweep_mesh._require_fanned_out(cell, stats)
