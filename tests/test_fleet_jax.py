"""Fleet simulator vs the discrete-event oracle, plus SPMD scaling checks."""
import jax
import numpy as np
import pytest

from repro.core.schedulers import make_policy
from repro.core.task import PASSIVE, TABLE1
from repro.sim.engine import run_policy
from repro.sim.fleet_jax import FleetPolicy, Profiles, simulate_fleet
from repro.sim.network import CloudLatencyModel, EdgeLatencyModel, trapezium
from repro.sim.workloads import task_stream

MODELS = [TABLE1[n] for n in PASSIVE]


def _engine_result(policy, duration=120_000.0, seed=0, theta_fn=None):
    em = EdgeLatencyModel(mean_frac=0.62, sd_frac=0.0, lo_frac=0.62,
                          hi_frac=0.62)
    cm = CloudLatencyModel(median_frac=0.80, sigma=1e-6, cold_start_p=0.0,
                           **({"latency_at": theta_fn} if theta_fn else {}))
    arr = task_stream(MODELS, n_drones=3, duration_ms=duration, seed=seed)
    return run_policy(make_policy(policy), arr, duration, seed=seed,
                      edge_model=em, cloud_model=cm, cloud_concurrency=512)


@pytest.mark.parametrize("policy", ["EDF-E+C", "DEMS", "GEMS"])
def test_fleet_matches_event_engine_approximately(policy):
    """Tick-based SPMD sim tracks the event-driven oracle within 10 %."""
    duration = 120_000.0
    oracle = _engine_result(policy, duration)
    final = simulate_fleet(MODELS, policy, n_edges=1, drones_per_edge=3,
                           duration_ms=duration, dt=25.0, cloud_slots=512,
                           edge_frac=0.62, cloud_frac=0.80, seed=0)
    got = float(np.asarray(final.n_success).sum())
    want = oracle.completed
    assert abs(got - want) / want < 0.10, (got, want)
    got_u = float(np.asarray(final.qos_utility).sum())
    assert abs(got_u - oracle.qos_utility) / abs(oracle.qos_utility) < 0.15


def test_fleet_dems_a_matches_oracle_under_trapezium():
    """§5.4 adaptation in the vmapped tick loop tracks the oracle's
    DEMS-A under the §8.5 trapezium θ trace (single edge)."""
    duration = 300_000.0
    oracle = _engine_result("DEMS-A", duration, theta_fn=trapezium())
    final = simulate_fleet(MODELS, "DEMS-A", n_edges=1, drones_per_edge=3,
                           duration_ms=duration, dt=25.0, cloud_slots=512,
                           edge_frac=0.62, cloud_frac=0.80,
                           theta_fn=trapezium(), seed=0)
    got = float(np.asarray(final.n_success).sum())
    want = oracle.completed
    assert abs(got - want) / want < 0.10, (got, want)
    got_u = float(np.asarray(final.qos_utility).sum())
    assert abs(got_u - oracle.qos_utility) / abs(oracle.qos_utility) < 0.15
    # the estimator must have reacted: some model's t̂ ends above static
    cur = np.asarray(final.adapt.current)
    static = np.asarray([m.t_cloud for m in MODELS])
    assert (cur > static + 1.0).any(), cur


def _scenario_agreement(scenario_name, policy="DEMS",
                        duration_ms=120_000.0):
    """Deterministic oracle vs fleet on a registry scenario; relative
    errors on completed tasks and QoS utility."""
    from repro.scenarios import (fleet_summary, get, run_scenario_fleet,
                                 run_scenario_oracle)

    spec = get(scenario_name, duration_ms=duration_ms)
    em = EdgeLatencyModel(mean_frac=0.62, sd_frac=0.0, lo_frac=0.62,
                          hi_frac=0.62)
    oracle = run_scenario_oracle(
        spec, policy, edge_model=em,
        cloud_model_overrides=dict(median_frac=0.80, sigma=1e-6,
                                   cold_start_p=0.0)).merged
    fleet = fleet_summary(run_scenario_fleet(spec, policy))
    d_done = abs(fleet["completed"] - oracle.completed) / oracle.completed
    d_qos = abs(fleet["qos_utility"] - oracle.qos_utility) / \
        abs(oracle.qos_utility)
    return oracle, fleet, d_done, d_qos


@pytest.mark.parametrize("policy", ["HPF", "CLD", "SJF-E+C", "SOTA1",
                                    "SOTA2", "GEMS-B"])
def test_fleet_matches_oracle_across_policy_matrix(policy):
    """Every §8.2 baseline (and the beyond-paper GEMS-B) agrees with the
    event-driven oracle within 10 % on a bursty registry scenario — the
    coverage that lets the one-program fleet sweep reproduce the paper's
    baseline comparison (Fig. 8) without falling back to the oracle."""
    oracle, fleet, d_done, d_qos = _scenario_agreement(
        "rush-hour", policy, duration_ms=90_000.0)
    assert d_done < 0.10, (policy, fleet["completed"], oracle.completed)
    assert d_qos < 0.10, (policy, fleet["qos_utility"], oracle.qos_utility)


def test_fleet_sota1_extension_is_scheduling_only():
    """SOTA1's 10 % deadline buffer buys insertions, not successes: the
    fleet must judge success at the *absolute* deadline, so SOTA1 can
    never out-complete the same mission where every completion counted
    (both sims agree — see the oracle's ``Task.sched_deadline``)."""
    from repro.scenarios import fleet_summary, get, run_scenario_fleet

    spec = get("rush-hour", duration_ms=60_000.0)
    sota1 = fleet_summary(run_scenario_fleet(spec, "SOTA1"))
    # settled tasks conserve: successes counted at abs deadline + misses
    # + drops add up the same as EDF-E+C (same arrivals, no stealing)
    epc = fleet_summary(run_scenario_fleet(spec, "EDF-E+C"))
    tot_sota1 = sota1["completed"] + sota1["missed"] + sota1["dropped"]
    tot_epc = epc["completed"] + epc["missed"] + epc["dropped"]
    assert abs(tot_sota1 - tot_epc) <= 0.02 * tot_epc
    # the buffer admits more edge inserts than plain EDF-E+C feasibility
    assert tot_sota1 > 0 and sota1["completed"] > 0


def test_fleet_cld_drops_negative_cloud_utility_tasks():
    """CLD routes everything cloud-ward and drops γ^C≤0 models (BP) —
    mirroring the oracle's admission check exactly."""
    final = simulate_fleet(MODELS, "CLD", n_edges=1, duration_ms=30_000.0,
                           cloud_slots=512)
    by_model = np.asarray(final.n_success).sum(0)
    bp = next(i for i, m in enumerate(MODELS) if m.gamma_cloud <= 0)
    assert by_model[bp] == 0                       # BP never completes
    assert np.asarray(final.n_drop).sum(0)[bp] > 0
    assert np.asarray(final.n_edge_exec).sum() == 0  # edge never used


def test_fleet_matches_oracle_under_saturated_cloud_pool():
    """cloud-crunch: 2 FaaS slots per edge + 4× burst — the fleet's
    finite-pool queue-wait must track the oracle's slot contention, not
    the old elastic cloud (which over-reported utility by >30 %)."""
    oracle, fleet, d_done, d_qos = _scenario_agreement("cloud-crunch")
    n_dropped = sum(s.dropped for s in oracle.per_model.values())
    assert n_dropped > 0.2 * oracle.generated        # pool really saturates
    assert d_done < 0.10, (fleet["completed"], oracle.completed)
    assert d_qos < 0.10, (fleet["qos_utility"], oracle.qos_utility)


def test_fleet_matches_oracle_under_bandwidth_fade():
    """bw-fade: deep cellular fade — the dense ``bw`` signal must apply
    the same signed transfer penalty as the oracle's shaped_delta."""
    oracle, fleet, d_done, d_qos = _scenario_agreement("bw-fade")
    assert d_done < 0.10, (fleet["completed"], oracle.completed)
    assert d_qos < 0.10, (fleet["qos_utility"], oracle.qos_utility)


def test_finite_pool_and_fade_degrade_fleet_utility():
    """Small pools and fades must hurt: the congestion scenarios exist to
    break the elastic-cloud optimism, so their fleet utility is strictly
    below the same mission with an ample pool / nominal bandwidth."""
    import dataclasses as dc

    from repro.scenarios import fleet_summary, get, run_scenario_fleet

    crunch = get("cloud-crunch", duration_ms=60_000.0)
    ample = dc.replace(crunch, cloud_concurrency=512)
    s_tight = fleet_summary(run_scenario_fleet(crunch, "DEMS"))
    s_ample = fleet_summary(run_scenario_fleet(ample, "DEMS"))
    assert s_tight["qos_utility"] < s_ample["qos_utility"]
    assert s_tight["completed"] < s_ample["completed"]

    fade = get("bw-fade", duration_ms=60_000.0)
    clear = dc.replace(fade, bandwidth=None)
    f_fade = fleet_summary(run_scenario_fleet(fade, "DEMS"))
    f_clear = fleet_summary(run_scenario_fleet(clear, "DEMS"))
    assert f_fade["qos_utility"] < f_clear["qos_utility"]


def test_fleet_dems_a_beats_dems_under_variability():
    """Paper Fig. 11: adaptation pays off on QoS when θ(t) swings."""
    kw = dict(n_edges=1, drones_per_edge=3, duration_ms=300_000.0,
              theta_fn=trapezium(), seed=0)
    adpt = simulate_fleet(MODELS, "DEMS-A", **kw)
    base = simulate_fleet(MODELS, "DEMS", **kw)
    assert float(np.asarray(adpt.qos_utility).sum()) >= \
        float(np.asarray(base.qos_utility).sum())


def test_fleet_dems_steals_and_beats_e_plus_c():
    kw = dict(n_edges=2, drones_per_edge=3, duration_ms=90_000.0)
    dems = simulate_fleet(MODELS, "DEMS", **kw)
    epc = simulate_fleet(MODELS, "EDF-E+C", **kw)
    assert np.asarray(dems.n_stolen).sum() > 0
    assert np.asarray(dems.qos_utility).sum() >= \
        np.asarray(epc.qos_utility).sum()


def test_fleet_scales_edges_linearly():
    """Weak scaling (paper §8.6): per-edge results independent of fleet size."""
    a = simulate_fleet(MODELS, "DEMS", n_edges=1, duration_ms=60_000.0,
                       seed=1)
    b = simulate_fleet(MODELS, "DEMS", n_edges=8, duration_ms=60_000.0,
                       seed=1)
    per_edge_a = float(np.asarray(a.n_success).sum())
    per_edge_b = float(np.asarray(b.n_success).sum()) / 8
    assert abs(per_edge_b - per_edge_a) / per_edge_a < 0.15


def test_fleet_gems_accrues_qoe():
    import dataclasses
    models = [dataclasses.replace(m, qoe_alpha=0.5, qoe_beta=100.0,
                                  qoe_window=10_000.0) for m in MODELS]
    final = simulate_fleet(models, "GEMS", n_edges=1,
                           duration_ms=60_000.0)
    assert float(np.asarray(final.qoe_utility).sum()) > 0
    assert int(np.asarray(final.windows_met).sum()) > 0


def test_fleet_gems_b_restrains_flood_once_window_is_lost():
    """At α=1.0 Alg. 1's rate check is absorbing: one failure loses the
    window for good, yet GEMS keeps flooding the cloud.  GEMS-B's
    winnability gate (per-window ``prev_lam`` arrival forecast) must keep
    strictly more of the still-salvageable work on the edge."""
    import dataclasses
    models = [dataclasses.replace(m, qoe_alpha=1.0, qoe_beta=100.0,
                                  qoe_window=10_000.0) for m in MODELS]
    kw = dict(n_edges=1, drones_per_edge=8, duration_ms=60_000.0,
              cloud_slots=4)
    gems = simulate_fleet(models, "GEMS", **kw)
    gems_b = simulate_fleet(models, "GEMS-B", **kw)
    edge_g = int(np.asarray(gems.n_edge_exec).sum())
    edge_b = int(np.asarray(gems_b.n_edge_exec).sum())
    assert edge_b > edge_g, (edge_b, edge_g)


def test_fleet_task_conservation():
    final = simulate_fleet(MODELS, "DEMS", n_edges=2, drones_per_edge=2,
                           duration_ms=60_000.0)
    done = (np.asarray(final.n_success).sum() + np.asarray(final.n_miss).sum()
            + np.asarray(final.n_drop).sum())
    generated = 2 * 2 * 60 * len(MODELS)
    # a handful of tasks may still be queued when the horizon ends
    assert generated * 0.97 <= done <= generated


def test_fleet_sharded_over_mesh_axis():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((jax.device_count(),), ("fleet",))
    final = simulate_fleet(MODELS, "DEMS", n_edges=4,
                           duration_ms=30_000.0, mesh=mesh)
    assert np.asarray(final.n_success).sum() > 0
