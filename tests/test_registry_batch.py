"""One-program registry sweeps: padding exactness, 2-D sharding parity,
depth-aware pool wait, estimator telemetry, and signal-stacking guards.

1. the padded cross-scenario batch (``compile_registry_batch`` /
   ``run_batch``) reproduces the per-scenario ``run_fleet`` loop exactly
   for every registry scenario × policy × seed;
2. ``pad_signals`` masks replicas to the max shape with exact no-op
   padding; ``stack_signals`` raises a ValueError naming the mismatched
   field instead of an opaque stack error;
3. a 2-D (replica, edge) mesh-sharded ``run_batch`` is bitwise identical
   to the unsharded program (subprocess with forced host devices);
4. the depth-aware ``_pool_wait`` k-th order statistic: min-based in the
   empty-queue case, deeper slots under queueing, identically zero in the
   elastic limit;
5. ``record_trace`` carries the per-tick t̂ out of the scan on a
   ``FleetResult`` without disturbing the final state.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.task import PASSIVE, TABLE1
from repro.scenarios import (ScenarioSpec, ThetaTrapezium,
                             compile_fleet, compile_registry_batch,
                             fleet_summary, get, names, run_registry_sweep,
                             run_scenario_fleet)
from repro.sim.fleet_jax import (FleetPolicy, FleetResult, Profiles,
                                 _pool_wait, build_fleet_batch, init_state,
                                 pad_signals, run_batch, stack_signals)

MODELS = [TABLE1[n] for n in PASSIVE]
SWEEP_DURATION_MS = 10_000.0
SWEEP_POLICIES = ("DEMS-A", "GEMS-B-COOP")
SWEEP_SEEDS = (0, 1)
# the six policies this PR adds to the fleet backend (README matrix)
NEW_POLICIES = ("HPF", "CLD", "SJF-E+C", "SOTA1", "SOTA2", "GEMS-B")


# ---------------------------------------------------------------------------
# (1) padded one-program sweep ≡ per-scenario run_fleet loop, all scenarios
# ---------------------------------------------------------------------------

def test_registry_batch_matches_per_scenario_loop_exactly():
    """Padded path: a cooperative policy keeps multi-edge replicas."""
    rows = run_registry_sweep(None, SWEEP_POLICIES, SWEEP_SEEDS,
                              duration_ms=SWEEP_DURATION_MS)
    assert len(rows) == len(names()) * len(SWEEP_POLICIES) * len(SWEEP_SEEDS)
    for row in rows:
        spec = get(row["scenario"], duration_ms=SWEEP_DURATION_MS,
                   seed=row["seed"])
        want = fleet_summary(run_scenario_fleet(spec, row["policy"]))
        got = {k: row[k] for k in want}
        assert got == want, (row["scenario"], row["policy"], row["seed"])


def test_registry_sweep_runs_full_policy_matrix_in_one_program():
    """All six newly-covered policies (plus DEMS as the reference) sweep
    through ``run_registry_sweep`` — a *single* compiled program, policy
    flags being runtime ``PolicyParams`` — and each run's summary equals
    its standalone ``run_fleet`` loop exactly."""
    pols = NEW_POLICIES + ("DEMS",)
    rows = run_registry_sweep(("baseline", "cloud-crunch"), pols, (0,),
                              duration_ms=SWEEP_DURATION_MS)
    assert len(rows) == 2 * len(pols)
    for row in rows:
        spec = get(row["scenario"], duration_ms=SWEEP_DURATION_MS,
                   seed=row["seed"])
        want = fleet_summary(run_scenario_fleet(spec, row["policy"]))
        got = {k: row[k] for k in want}
        assert got == want, (row["scenario"], row["policy"])
    # the matrix really exercised distinct decision rules: cloud-only CLD
    # must differ from edge-only HPF on the same mission
    by = {(r["scenario"], r["policy"]): r for r in rows}
    assert by[("baseline", "CLD")]["qos_utility"] != \
        by[("baseline", "HPF")]["qos_utility"]


def test_registry_batch_edge_flattened_matches_loop_exactly():
    """Non-cooperative sweep: each (run, edge) becomes a 1-edge replica
    (zero edge padding) — per-run summaries still match the loop."""
    rows = run_registry_sweep(("rush-hour", "roaming-vips", "hetero-edges"),
                              ("DEMS", "EDF-E+C"), (0,),
                              duration_ms=SWEEP_DURATION_MS)
    for row in rows:
        spec = get(row["scenario"], duration_ms=SWEEP_DURATION_MS,
                   seed=row["seed"])
        want = fleet_summary(run_scenario_fleet(spec, row["policy"]))
        got = {k: row[k] for k in want}
        assert got == want, (row["scenario"], row["policy"])


def test_registry_batch_row_index_order_and_lanes():
    batch, rows = compile_registry_batch(("baseline", "rush-hour"),
                                         ("DEMS", "EDF-E+C"), (0, 1),
                                         duration_ms=5_000.0)
    assert [(r.scenario, r.policy, r.seed) for r in rows] == [
        ("baseline", "DEMS", 0), ("baseline", "DEMS", 1),
        ("baseline", "EDF-E+C", 0), ("baseline", "EDF-E+C", 1),
        ("rush-hour", "DEMS", 0), ("rush-hour", "DEMS", 1),
        ("rush-hour", "EDF-E+C", 0), ("rush-hour", "EDF-E+C", 1)]
    # non-coop sweep → edge-flattened: 4 baseline lanes + 8 rush-hour
    # lanes (2 edges each), disjoint and in order
    assert [r.lanes for r in rows[:4]] == [(0,), (1,), (2,), (3,)]
    assert [r.lanes for r in rows[4:]] == [(4, 5), (6, 7), (8, 9),
                                           (10, 11)]
    assert batch.signals.arrive.shape[0] == 12
    assert batch.signals.arrive.shape[2] == 1          # no edge padding
    # cooperative sweep → padded multi-edge replicas, one lane per run
    batch2, rows2 = compile_registry_batch(("baseline", "rush-hour"),
                                           ("DEMS-COOP",), (0,),
                                           duration_ms=5_000.0)
    assert [r.lanes for r in rows2] == [(0,), (1,)]
    assert batch2.signals.arrive.shape[:3] == (2, 200, 2)


# ---------------------------------------------------------------------------
# (2) pad_signals / stack_signals guards
# ---------------------------------------------------------------------------

def test_pad_signals_masks_to_max_shape():
    a = compile_fleet(get("baseline", duration_ms=5_000.0))       # 1 edge
    b = compile_fleet(get("roaming-vips", duration_ms=10_000.0))  # 3 edges
    sig = pad_signals([a, b])
    t, e, m = sig.arrive.shape[1:]
    assert (t, e, m) == (400, 3, 6)       # max ticks/edges/models
    valid = np.asarray(sig.valid)
    assert valid[0, :200, :1].all() and not valid[0, 200:].any() \
        and not valid[0, :, 1:].any()
    assert valid[1].all()
    # padded models never arrive; order stays a permutation everywhere
    assert not np.asarray(sig.arrive)[0, :, :, 4:].any()
    assert (np.sort(np.asarray(sig.order), axis=-1)
            == np.arange(m)).all()


def test_stack_signals_names_mismatched_field():
    a = compile_fleet(get("baseline", duration_ms=5_000.0))
    b = compile_fleet(get("rush-hour", duration_ms=5_000.0))  # 2 edges
    with pytest.raises(ValueError, match="field 'theta'"):
        stack_signals([a, b])
    with pytest.raises(ValueError, match="pad_signals"):
        stack_signals([a, b])


def test_build_fleet_batch_rejects_mixed_adapt_windows():
    sig = compile_fleet(get("baseline", duration_ms=5_000.0))
    runs = [(MODELS, FleetPolicy(adaptive=True, adapt_window=10), sig, 16),
            (MODELS, FleetPolicy(adaptive=True, adapt_window=5), sig, 16)]
    with pytest.raises(ValueError, match="adapt_window"):
        build_fleet_batch(runs)


# ---------------------------------------------------------------------------
# (3) 2-D (replica, edge) mesh sharding ≡ unsharded, bitwise
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import jax, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.scenarios import compile_registry_batch
    from repro.sim.fleet_jax import run_batch
    batch, rows = compile_registry_batch(
        ("baseline", "rush-hour"), ("DEMS", "DEMS-COOP"), (0, 1),
        duration_ms=8_000.0)
    ref = run_batch(batch)
    mesh = make_mesh((2, 2), ("replica", "edge"))
    got = run_batch(batch, mesh=mesh)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("SHARDING-PARITY-OK", len(rows), jax.device_count())
""")


def test_2d_sharded_run_batch_bitwise_matches_unsharded():
    """The child runs on the CPU only (``JAX_PLATFORMS=cpu``, four forced
    host devices): a test process may hold an accelerator, and a child
    that reached for it would fail or hang."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               + os.environ.get("XLA_FLAGS", ""),
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDING-PARITY-OK 8 4" in proc.stdout


# ---------------------------------------------------------------------------
# (4) depth-aware pool queue-wait (k-th order statistic)
# ---------------------------------------------------------------------------

def _state_with(busy, n_pending):
    prof = Profiles.build(MODELS)
    st = init_state(prof, cloud_slots=len(busy))
    cq_valid = st.cq.valid.at[:n_pending].set(True)
    return st._replace(cloud_busy_until=jnp.asarray(busy, jnp.float32),
                       cq=st.cq._replace(valid=cq_valid))


def test_pool_wait_empty_queue_reduces_to_min_based_estimate():
    st = _state_with([300.0, 100.0, 200.0], 0)
    assert float(_pool_wait(st, 40.0)) == 60.0      # min(busy) − now


def test_pool_wait_uses_queue_depth_order_statistic():
    st = _state_with([300.0, 100.0, 200.0], 2)      # 2 tasks ahead → k=2
    assert float(_pool_wait(st, 40.0)) == 260.0     # 3rd-soonest slot
    st = _state_with([300.0, 100.0, 200.0], 7)      # clamps at pool depth
    assert float(_pool_wait(st, 40.0)) == 260.0


def test_pool_wait_elastic_limit_identically_zero():
    st = _state_with([0.0] * 8, 5)                  # ample free pool
    assert float(_pool_wait(st, 123.0)) == 0.0


def test_pool_wait_ignores_steal_only_parkees():
    st = _state_with([300.0, 100.0, 200.0], 2)
    st = st._replace(cq=st.cq._replace(
        steal_only=st.cq.steal_only.at[:2].set(True)))
    assert float(_pool_wait(st, 40.0)) == 60.0      # back to k=0


# ---------------------------------------------------------------------------
# (5) estimator telemetry: per-tick t̂ trace on FleetResult
# ---------------------------------------------------------------------------

def _trace_spec():
    return ScenarioSpec(
        name="trace-test", duration_ms=60_000.0,
        theta=ThetaTrapezium(ramp_up=(5_000.0, 15_000.0),
                             ramp_down=(45_000.0, 55_000.0)))


def test_record_trace_returns_fleet_result_with_t_hat():
    spec = _trace_spec()
    res = run_scenario_fleet(spec, "DEMS-A", record_trace=True)
    assert isinstance(res, FleetResult)
    n_ticks = int(spec.duration_ms / 25.0)
    assert res.t_hat.shape == (n_ticks, spec.n_edges, len(spec.models))
    static = np.asarray([m.t_cloud for m in spec.models])
    t_hat = np.asarray(res.t_hat)
    assert (t_hat[0] == static).all()               # starts at Table-1 t̂
    assert (t_hat.max(axis=(1, 2)) > static.max() + 1.0).any()  # reacted


def test_record_trace_leaves_final_state_untouched():
    spec = _trace_spec()
    res = run_scenario_fleet(spec, "DEMS-A", record_trace=True)
    plain = run_scenario_fleet(spec, "DEMS-A")
    for a, b in zip(jax.tree.leaves(res.final), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
