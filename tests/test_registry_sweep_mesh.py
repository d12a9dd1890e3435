"""The registry sweep on a mesh: ``run_registry_sweep(mesh="auto")``
against the unsharded sweep, its :class:`~repro.obs.prof.SweepStats`
record and its ``fleet.sweep.*`` profiler spans.

The sharded cases run in one child process on the CPU only
(``JAX_PLATFORMS=cpu``, four forced host devices), as
``tests/test_registry_batch.py``'s 2-D case does: a test process may
hold an accelerator, and a child that reached for it would fail or hang.
The child sweeps a two-edge scenario under DEMS-A and DEMS-A-COOP with
4 seeds and 8 s missions, where every bucket's lanes are a multiple of
4, so every bucket spans all four devices; and a one-edge scenario under
DEMS-A-COOP with 3 seeds and 1 s missions, whose bucket of 3 lanes falls
back to three devices (the largest count that divides its lanes), and
which runs once more under the profiler.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_CHILD = textwrap.dedent("""
    import glob, json, tempfile
    import jax, numpy as np
    from jax.profiler import ProfileData
    from repro.obs.prof import SweepStats
    from repro.scenarios.compile import compile_registry_groups
    from repro.scenarios.runner import run_registry_sweep
    import repro.sim.fleet_jax as fj

    # (name, scenarios, policies, seeds, mission ms)
    CASES = (("four", ("flash-crowd",), ("DEMS-A", "DEMS-A-COOP"),
              (0, 1, 2, 3), 8e3),
             ("fallback", ("cloud-crunch",), ("DEMS-A-COOP",), (0, 1, 2),
              1e3))
    real = fj.run_batch
    finals = []

    def keeping(batch, *a, **k):
        out = real(batch, *a, **k)
        finals.append(jax.device_get(out))
        return out

    fj.run_batch = keeping

    def sweep(scenarios, policies, seeds, duration_ms, **kw):
        finals.clear()
        rows = run_registry_sweep(scenarios, policies, seeds,
                                  duration_ms=duration_ms, **kw)
        return rows, list(finals)

    def same(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    def buckets(stats):
        return [[b.lanes, b.chips, b.ticks] for b in stats.buckets]

    # one record over both unsharded sweeps: it sums the passes it is
    # given to
    unsharded = SweepStats()
    out = {}
    for name, *case in CASES:
        plain, plain_ends = sweep(*case, stats=unsharded)
        stats = SweepStats()
        auto, auto_ends = sweep(*case, mesh="auto", stats=stats)
        bare, _ = sweep(*case, mesh="auto")
        lowered = [tuple(int(n) for n in b.signals.arrive.shape[:2])
                   for b, _ in compile_registry_groups(
                       *case[:3], duration_ms=case[3])]
        out[name] = dict(
            rows_equal=auto == plain, rows_with_stats=auto == bare,
            n_rows=len(auto),
            ends_equal=len(auto_ends) == len(plain_ends) and all(
                same(a, b) for a, b in zip(auto_ends, plain_ends)),
            lowered=lowered, buckets=buckets(stats),
            timed=all(b.run_s > 0 and b.gather_s > 0
                      for b in stats.buckets)
            and stats.lower_s > 0 and stats.summarize_s > 0)
    out["unsharded"] = dict(buckets=buckets(unsharded),
                            bucket_ticks=unsharded.bucket_ticks,
                            run_s=unsharded.run_s,
                            run_s_summed=sum(b.run_s
                                             for b in unsharded.buckets))

    # the last case's sweep again (its programs compiled), traced
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    sweep(*case, mesh="auto")
    jax.profiler.stop_trace()
    path = glob.glob(logdir + "/**/*.xplane.pb", recursive=True)[0]
    out["spans"] = list(
        [ev.name, dict(ev.stats)]
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
        for ev in line.events if ev.name.startswith("fleet.sweep."))
    out["devices"] = jax.device_count()
    print("SWEEP-MESH " + json.dumps(out))
""")


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
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SWEEP-MESH ")][-1]
    out = json.loads(line.split(" ", 1)[1])
    assert out["devices"] == 4
    return out


@pytest.mark.parametrize("case", ["four", "fallback"])
def test_auto_mesh_sweep_bitwise_equals_unsharded(child, case):
    got = child[case]
    assert got["rows_equal"] and got["ends_equal"]
    assert got["n_rows"] == (8 if case == "four" else 3)


@pytest.mark.parametrize("case", ["four", "fallback"])
def test_stats_record_matches_the_lowering(child, case):
    got = child[case]
    lanes = [n for n, _ in got["lowered"]]
    chips = [max(d for d in range(1, 5) if n % d == 0) for n in lanes]
    assert got["buckets"] == [[n, c, t] for (n, t), c
                              in zip(got["lowered"], chips)]
    assert got["timed"]
    assert set(chips) == ({4} if case == "four" else {3})


@pytest.mark.parametrize("case", ["four", "fallback"])
def test_rows_identical_with_and_without_stats(child, case):
    assert child[case]["rows_with_stats"]


def test_traced_sweep_emits_the_four_spans(child):
    names = [n for n, _ in child["spans"]]
    assert names.count("fleet.sweep.lower") == 1
    for span in ("fleet.sweep.bucket", "fleet.sweep.gather",
                 "fleet.sweep.summarize"):
        assert names.count(span) == 1, (span, names)
    # a bucket's span carries its index, lanes, chips and ticks: one
    # bucket of 3 one-edge runs over three devices, 40 ticks
    buckets = [a for n, a in child["spans"] if n == "fleet.sweep.bucket"]
    assert buckets == [dict(bucket=0, lanes=3, chips=3, ticks=40)]


def test_unsharded_stats_count_one_chip_a_bucket(child):
    """Without a mesh every bucket runs on one device, and a record
    spans the passes it is given to: both cases' unsharded sweeps."""
    got = child["unsharded"]
    lowered = child["four"]["lowered"] + child["fallback"]["lowered"]
    assert got["buckets"] == [[n, 1, t] for n, t in lowered]
    assert got["bucket_ticks"] == sum(t for _, t in lowered)
    assert got["run_s"] == got["run_s_summed"] > 0
