"""Fleet simulator vs the discrete-event oracle, plus SPMD scaling checks."""
import functools
import re

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


@functools.lru_cache(maxsize=None)
def _tick_op_names(policy: str) -> tuple:
    """The ``op_name`` metadata of the 8-edge tick program, lowered."""
    from repro.sim.fleet_jax import FleetProgram, default_signals

    pol = FleetPolicy.from_name(policy)
    prog = FleetProgram.for_policy(pol)
    prof = Profiles.build(MODELS)
    sig = default_signals(len(MODELS), n_edges=8, duration_ms=200.0)
    hlo = prog.lower(prof, pol.params(), prog.init(prof, pol, 8),
                     sig).as_text(dialect="hlo", debug_info=True)
    return tuple(re.findall(r'op_name="([^"]+)"', hlo))


@pytest.mark.parametrize("policy,scope", [
    ("DEMS-A", "resolve_cloud"), ("DEMS-A", "route_arrivals"),
    ("DEMS-A", "edge_execute"), ("DEMS-A", "gems_act"),
    ("DEMS-A", "masked_argext"), ("DEMS-A-COOP", "peer_offload")])
def test_tick_program_names_its_phases(policy, scope):
    """Each tick phase runs under its ``jax.named_scope``, so a profile
    attributes the phase's operations to it (JAX wraps a scope traced
    under ``vmap`` as ``vmap(<scope>)``)."""
    ops = _tick_op_names(policy)
    assert any(scope in re.split(r"[/()]", o) for o in ops)


# ---------------------------------------------------------------------------
# arrival routing without scatters
# ---------------------------------------------------------------------------

def _scatter_segment(data, segment_ids, num_segments, op="sum"):
    seg = jax.ops.segment_sum if op == "sum" else jax.ops.segment_max
    return seg(data, segment_ids, num_segments=num_segments)


def _offer_cloud_many_scatter(st, prof, pp, now, models, deadlines, t_edges,
                              enable):
    """The cloud admission's scatter form: accepted offers are scattered
    by rank, then gathered back to the free slots in slot order."""
    import jax.numpy as jnp

    from repro.core import jax_sched as js
    from repro.sim import fleet_jax as fj

    t_cur = fj._t_cloud_cur(st, prof, pp, now)
    t_hat = t_cur[models]
    feasible = now + t_hat <= deadlines
    negative = (prof.gamma_c[models] <= 0) & ~pp.cloud_neg_ok
    trig_steal = jnp.where(negative, deadlines - t_edges,
                           jnp.maximum(now, deadlines - t_hat
                                       - pp.cloud_margin))
    accept_steal = enable & feasible & jnp.where(negative,
                                                 trig_steal >= now, True)
    accept_plain = enable & feasible & ~negative
    accept = pp.use_cloud & jnp.where(pp.stealing, accept_steal,
                                      accept_plain)
    trigger = jnp.where(pp.stealing, trig_steal, now)
    steal_only = jnp.where(pp.stealing, negative, False)

    free = ~st.cq.valid
    qc = free.shape[0]
    ai = accept.astype(jnp.int32)
    arank = jnp.cumsum(ai) - ai
    pushed = accept & (arank < free.sum())
    tgt = jnp.where(pushed, arank, qc)

    def by_rank(vals):
        return jnp.zeros(qc, vals.dtype).at[tgt].set(vals, mode="drop")

    fi = free.astype(jnp.int32)
    frank = jnp.cumsum(fi) - fi
    fill = free & (frank < pushed.sum())

    def put(old, vals):
        return jnp.where(fill, by_rank(vals)[frank], old)

    st = st._replace(
        cq=js.CloudQueue(
            valid=st.cq.valid | fill,
            trigger=put(st.cq.trigger, trigger),
            t_edge=put(st.cq.t_edge, t_edges),
            deadline=put(st.cq.deadline, deadlines),
            steal_only=put(st.cq.steal_only, steal_only),
            rank=put(st.cq.rank, prof.steal_rank[models])),
        cq_model=put(st.cq_model, models),
        cq_blocked=st.cq_blocked & ~fill)
    skip = enable & ~accept & pp.use_cloud & pp.adaptive
    st = st._replace(adapt=js.adapt_feed_batch(
        st.adapt, models, jnp.zeros_like(skip), jnp.zeros_like(skip),
        jnp.zeros_like(t_hat), skip, now, prof.t_cloud, pp.adapt_eps,
        pp.adapt_cooling_ms, with_obs=False))
    return st, pushed, accept


def _random_offers(seed, policy, taken, offered, n_edges=48):
    """A fleet of random per-edge states and one batch of cloud offers
    (33 a edge: a full edge queue of victims and the arrival)."""
    import jax.numpy as jnp

    from repro.sim import fleet_jax as fj

    rng = np.random.default_rng(seed)
    m_real, m = 6, 8          # two inert padded models at +inf
    qc, k = fj.CLOUD_CAP, fj.EDGE_CAP + 1

    def pad(x, val):
        return np.concatenate([x, np.full(m - m_real, val)]).astype(
            np.float32)

    t_cloud = rng.uniform(100, 600, m_real)
    rank = rng.uniform(-1, 1, m_real)
    rank[0] = -0.0
    prof = fj.Profiles(
        t_edge=pad(rng.uniform(50, 300, m_real), np.inf),
        t_cloud=pad(t_cloud, np.inf),
        deadline=pad(rng.uniform(300, 1500, m_real), np.inf),
        gamma_e=pad(rng.uniform(0, 3, m_real), 0.0),
        gamma_c=pad(rng.uniform(-2, 3, m_real), 0.0),
        cost_e=pad(rng.uniform(0, 1, m_real), 0.0),
        cost_c=pad(rng.uniform(0, 1, m_real), 0.0),
        steal_rank=pad(rank, 0.0),
        qoe_alpha=pad(np.full(m_real, 0.9), 0.0),
        qoe_beta=pad(np.ones(m_real), 0.0),
        qoe_window=pad(np.full(m_real, 60_000.0), np.inf))
    prof = fj.Profiles(*(jnp.asarray(x) for x in prof))
    pp = FleetPolicy.from_name(policy).params()

    def f32(*shape, lo=0.0, hi=1.0):
        return rng.uniform(lo, hi, shape).astype(np.float32)

    now = f32(n_edges, lo=1000, hi=5000)
    st = jax.tree.map(lambda a: np.broadcast_to(a, (n_edges,) + a.shape),
                      fj.init_state(prof))
    cur = (np.asarray(prof.t_cloud)[None, :] * f32(n_edges, m, lo=0.8,
                                                   hi=1.5))
    st = st._replace(
        cq=st.cq._replace(
            valid=rng.random((n_edges, qc)) < taken,
            trigger=f32(n_edges, qc, hi=5000), t_edge=f32(n_edges, qc,
                                                          hi=400),
            deadline=f32(n_edges, qc, hi=8000),
            steal_only=rng.random((n_edges, qc)) < 0.3,
            rank=f32(n_edges, qc, lo=-1)),
        cq_model=rng.integers(0, m, (n_edges, qc), dtype=np.int32),
        cq_blocked=rng.random((n_edges, qc)) < 0.3,
        cloud_busy_until=now[:, None] + f32(n_edges,
                                            fj.CLOUD_SLOTS, lo=-300, hi=600),
        adapt=st.adapt._replace(
            current=np.where(np.isfinite(cur), cur, np.inf).astype(
                np.float32),
            cooling_start=np.where(rng.random((n_edges, m)) < 0.5, -1.0,
                                   now[:, None] - f32(n_edges, m,
                                                      hi=20_000)).astype(
                np.float32)))
    st = jax.tree.map(jnp.asarray, st)
    t_edges = f32(n_edges, k, lo=50, hi=400)
    t_edges[:, 3] = -0.0
    offers = dict(
        models=jnp.asarray(rng.integers(0, m, (n_edges, k), dtype=np.int32)),
        deadlines=jnp.asarray(now[:, None] + f32(n_edges, k, lo=-100,
                                                 hi=3000)),
        t_edges=jnp.asarray(t_edges),
        enable=jnp.asarray(rng.random((n_edges, k)) < offered))
    return prof, pp, st, jnp.asarray(now), offers


_OFFER_CASES = {
    # name: (policy, share of cloud-queue slots taken, share offered, what
    # the batch must show for the case to test what it names)
    "empty_queue": ("DEMS-A", 0.0, 0.5, lambda p, a: p.any()),
    "full_queue": ("DEMS-A", 1.0, 0.5, lambda p, a: a.any() & ~p.any()),
    "no_offers": ("DEMS-A", 0.5, 0.0, lambda p, a: ~a.any()),
    "all_offered": ("DEMS-A", 0.3, 1.0, lambda p, a: p.sum() > 0),
    "capacity_drops": ("DEMS-A", 0.8, 1.0, lambda p, a: (a & ~p).any()),
    "sjf_cloud_neg_ok": ("SJF-E+C", 0.5, 0.7, lambda p, a: p.any()),
    "stealing": ("DEMS", 0.5, 0.7, lambda p, a: p.any()),
}


@pytest.mark.parametrize("case", sorted(_OFFER_CASES))
def test_offer_cloud_many_matches_scatter_form(case, monkeypatch):
    """The one-hot compaction admits to the same cloud-queue slots, with
    the same fields, bit for bit, as the scatter-and-gather form, and the
    estimator's skip feed matches its segment-reduction form."""
    from repro.core import jax_sched as js
    from repro.sim.fleet_jax import _offer_cloud_many

    policy, taken, offered, shows = _OFFER_CASES[case]
    prof, pp, st, now, o = _random_offers(
        sorted(_OFFER_CASES).index(case), policy, taken, offered)
    args = (st, now, o["models"], o["deadlines"], o["t_edges"], o["enable"])

    def run(fn):
        return jax.vmap(lambda s, n, *a: fn(s, prof, pp, n, *a))(*args)

    got = run(_offer_cloud_many)
    with monkeypatch.context() as mp:
        mp.setattr(js, "onehot_segment", _scatter_segment)
        want = run(_offer_cloud_many_scatter)
    assert bool(shows(np.asarray(want[1]), np.asarray(want[2])))
    leaves_got, tree = jax.tree.flatten(got)
    leaves_want, tree_want = jax.tree.flatten(want)
    assert tree == tree_want
    for path, g, w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                          leaves_got, leaves_want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, path[0]
        assert g.tobytes() == w.tobytes(), jax.tree_util.keystr(path[0])


def _primitive_names(jaxpr) -> set:
    """Every primitive of a jaxpr and of the jaxprs nested in it."""
    from jax.extend import core as jcore

    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(x, jcore.ClosedJaxpr):
                    out |= _primitive_names(x.jaxpr)
                elif isinstance(x, jcore.Jaxpr):
                    out |= _primitive_names(x)
    return out


def test_route_arrival_has_no_scatter():
    """Under the fleet ``vmap`` a scatter runs one index at a time on the
    TPU: the per-arrival path places, counts and feeds the estimator by
    one-hot compare-select, and applies no match with a matmul (a float
    dot on the TPU rounds through bf16 passes)."""
    import jax.numpy as jnp

    from repro.sim import fleet_jax as fj

    prof = Profiles.build(MODELS)
    pp = FleetPolicy.from_name("DEMS-A").params()
    n = 4
    st = jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                      fj.init_state(prof))

    def route(s, model, arrive, load_mult):
        return fj._route_arrival(s, None, prof, pp, jnp.float32(100.0),
                                 model, arrive, load_mult)[0]

    prims = _primitive_names(jax.make_jaxpr(jax.vmap(route))(
        st, jnp.zeros(n, jnp.int32), jnp.ones(n, bool),
        jnp.ones(n, jnp.float32)).jaxpr)
    assert "gather" in prims           # the walk does reach the route's ops
    assert not {p for p in prims if p.startswith("scatter")}, prims
    assert "dot_general" not in prims
