"""Property tests: JAX decision kernels ≡ the discrete-event oracle.

Each of the paper's scheduling decisions (feasibility, victims, Eqn-3
migration, steal selection, GEMS rescheduling, DEMS-A adaptation) is
implemented twice — as Python list code in ``sim.engine`` and as masked
``jnp`` kernels in ``core.jax_sched``.  Hypothesis drives both with random
queue states and asserts exact agreement.
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without the [test] extra: vendored shim
    from _minihyp import given, settings, strategies as st  # noqa: F401

from repro.core import jax_sched as js
from repro.core.schedulers import AdaptiveEstimator, make_policy
from repro.core.task import TABLE1, Task
from repro.sim.engine import Arrival, Simulator

MODELS = list(TABLE1.values())
M = len(MODELS)
GAMMA_E = jnp.array([m.gamma_edge for m in MODELS], jnp.float32)
GAMMA_C = jnp.array([m.gamma_cloud for m in MODELS], jnp.float32)
T_EDGE = jnp.array([m.t_edge for m in MODELS], jnp.float32)
T_CLOUD = jnp.array([m.t_cloud for m in MODELS], jnp.float32)
CAP = 12


def _sim(policy="DEMS"):
    arrivals = [Arrival(0.0, m) for m in MODELS]
    s = Simulator(make_policy(policy), arrivals, duration=1.0, seed=0)
    s._heap.clear()
    return s


task_st = st.tuples(st.integers(0, M - 1), st.integers(0, 300))

queue_st = st.lists(task_st, min_size=0, max_size=CAP - 2)


def _build_queue(entries, uid0=100):
    """Sorted task list (oracle) + EdgeQueue arrays (jax), identically
    ordered: stable sort by EDF key."""
    tasks = [Task(uid=uid0 + i, model=MODELS[mi], created=float(c * 10))
             for i, (mi, c) in enumerate(entries)]
    tasks.sort(key=lambda t: t.abs_deadline)   # stable → seq = position
    q = js.empty_edge_queue(CAP)
    for i, t in enumerate(tasks):
        q, ok = js.edge_push(q, t.abs_deadline, i, t.model.t_edge,
                             t.sched_deadline,
                             MODELS.index(t.model))
        assert bool(ok)
    return tasks, q


@settings(max_examples=120, deadline=None)
@given(queue_st, task_st, st.integers(0, 200), st.integers(0, 80))
def test_insert_feasibility_matches_oracle(entries, new, now10, busy10):
    now, busy = float(now10 * 10), float(busy10 * 10)
    tasks, q = _build_queue(entries)
    sim = _sim()
    sim.edge_queue = tasks
    sim.now = now
    sim.edge_busy_until = now + busy
    t_new = Task(uid=1, model=MODELS[new[0]], created=float(new[1] * 10))
    pos = sim._insert_pos(t_new)
    want = sim._feasible_at(sim.edge_queue, pos, t_new)
    got = bool(js.insert_feasible(q, now, busy, t_new.abs_deadline,
                                  t_new.model.t_edge, t_new.sched_deadline))
    assert got == want


@settings(max_examples=120, deadline=None)
@given(queue_st, task_st, st.integers(0, 200), st.integers(0, 80))
def test_victims_match_oracle(entries, new, now10, busy10):
    now, busy = float(now10 * 10), float(busy10 * 10)
    tasks, q = _build_queue(entries)
    sim = _sim()
    sim.edge_queue = tasks
    sim.now = now
    sim.edge_busy_until = now + busy
    t_new = Task(uid=1, model=MODELS[new[0]], created=float(new[1] * 10))
    pos = sim._insert_pos(t_new)
    want = {t.uid for t in sim._victims_of_insert(pos, t_new)}
    mask = np.asarray(js.victim_mask(q, now, busy, t_new.abs_deadline,
                                     t_new.model.t_edge))
    got = {tasks[i].uid for i in range(len(tasks)) if mask[i]}
    assert got == want


@settings(max_examples=120, deadline=None)
@given(queue_st, task_st, st.integers(0, 200))
def test_migration_decision_matches_oracle(entries, new, now10):
    now = float(now10 * 10)
    tasks, q = _build_queue(entries)
    if not tasks:
        return
    t_new = Task(uid=1, model=MODELS[new[0]], created=float(new[1] * 10))
    victims = tasks[: max(1, len(tasks) // 2)]
    vmask = jnp.array([t in victims for t in tasks] +
                      [False] * (CAP - len(tasks)))
    pol = make_policy("DEMS")
    want = pol.migration_decision(t_new, victims, now, lambda m: m.t_cloud)
    got = bool(js.migration_decision(
        q, vmask, now, MODELS.index(t_new.model), t_new.abs_deadline,
        GAMMA_E, GAMMA_C, T_CLOUD))
    assert got == want


cloud_task_st = st.tuples(st.integers(0, M - 1), st.integers(0, 300))


@settings(max_examples=120, deadline=None)
@given(queue_st,
       st.lists(cloud_task_st, min_size=0, max_size=CAP - 2),
       st.integers(0, 200))
def test_steal_selection_matches_oracle(entries, cloud_entries, now10):
    now = float(now10 * 10)
    tasks, q = _build_queue(entries)
    sim = _sim("DEMS")
    sim.edge_queue = list(tasks)
    sim.now = now
    sim.edge_busy_until = now          # executor idle, about to pick
    cloud_tasks = []
    cq = js.empty_cloud_queue(CAP)
    for i, (mi, c) in enumerate(cloud_entries):
        t = Task(uid=500 + i, model=MODELS[mi], created=float(c * 10))
        t.steal_only = t.model.gamma_cloud <= 0
        cloud_tasks.append(t)
        cq, ok = js.cloud_push(cq, now, t.model.t_edge, t.abs_deadline,
                               t.steal_only, t.model.steal_rank())
        assert bool(ok)
    sim.cloud_pending = list(cloud_tasks)
    want = sim._try_steal()
    got_idx = int(js.steal_select(cq, q, now, 0.0,
                                  float(sim.min_edge_t)))
    if want is None:
        assert got_idx == -1
    else:
        assert got_idx >= 0
        got = cloud_tasks[got_idx]
        # ties in (steal_only, rank) may pick a different but equal task
        assert (got.steal_only, got.model.steal_rank()) == \
            (want.steal_only, want.model.steal_rank())


@settings(max_examples=80, deadline=None)
@given(queue_st, st.integers(0, M - 1), st.integers(0, 200))
def test_gems_mask_matches_oracle(entries, lag_model, now10):
    now = float(now10 * 10)
    tasks, q = _build_queue(entries)
    sim = _sim("GEMS")
    sim.edge_queue = list(tasks)
    sim.now = now
    m = MODELS[lag_model]
    sim._gems_rescan(m)
    want = {t.uid for t in tasks if t.gems_rescheduled}
    mask = np.asarray(js.gems_reschedule_mask(
        q, now, lag_model, T_CLOUD, GAMMA_C))
    got = {tasks[i].uid for i in range(len(tasks)) if mask[i]}
    assert got == want


@settings(max_examples=60, deadline=None)
@given(st.lists(st.floats(50, 2000), min_size=1, max_size=30),
       st.integers(2, 10))
def test_adaptive_observe_matches_oracle(observations, w):
    est = AdaptiveEstimator(static=400.0, w=w, eps=10.0)
    stj = js.adapt_init(jnp.array([400.0]), w=w)
    for o in observations:
        est.observe(o)
        stj = js.adapt_observe(stj, 0, o, eps=10.0)
    assert float(stj.current[0]) == pytest.approx(est.current, rel=1e-6)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.booleans(), st.floats(0, 40_000)),
                min_size=1, max_size=25))
def test_adaptive_skip_cooling_matches_oracle(events):
    est = AdaptiveEstimator(static=400.0, w=4, eps=10.0, t_cp=10_000.0)
    stj = js.adapt_init(jnp.array([400.0]), w=4)
    for _ in range(4):
        est.observe(900.0)
        stj = js.adapt_observe(stj, 0, 900.0, eps=10.0)
    events = sorted(events, key=lambda e: e[1])
    static = jnp.array([400.0])
    for sent, t in events:
        if sent:
            est.on_sent()
            stj = js.adapt_on_sent(stj, 0)
        else:
            est.on_skip(t)
            stj = js.adapt_on_skip(stj, 0, t, static, t_cp=10_000.0)
        assert float(stj.current[0]) == pytest.approx(est.current)


adapt_event_st = st.tuples(
    st.integers(0, 1),                     # model index
    st.integers(0, 2),                     # 0 = observe, 1 = skip, 2 = sent
    st.floats(50, 2000),                   # observed duration (if observe)
    st.integers(1, 2_000))                 # time advance [ms]


@settings(max_examples=80, deadline=None)
@given(st.lists(adapt_event_st, min_size=1, max_size=40),
       st.integers(2, 8))
def test_adaptive_mixed_sequence_matches_oracle(events, w):
    """AdaptState mirrors AdaptiveEstimator step-for-step on arbitrary
    interleavings of observe / on_skip / on_sent across two models."""
    t_cp = 5_000.0
    ests = [AdaptiveEstimator(static=400.0, w=w, eps=10.0, t_cp=t_cp)
            for _ in range(2)]
    static = jnp.array([400.0, 400.0])
    stj = js.adapt_init(static, w=w)
    now = 0.0
    for m, kind, val, dt_ms in events:
        now += float(dt_ms)
        if kind == 0:
            ests[m].observe(val)
            stj = js.adapt_observe(stj, m, val, eps=10.0)
        elif kind == 1:
            ests[m].on_skip(now)
            stj = js.adapt_on_skip(stj, m, now, static, t_cp=t_cp)
        else:
            ests[m].on_sent()
            stj = js.adapt_on_sent(stj, m)
        for k in range(2):
            assert float(stj.current[k]) == \
                pytest.approx(ests[k].current, rel=1e-6)
            want_cs = ests[k]._cooling_start
            got_cs = float(stj.cooling_start[k])
            if want_cs is None:
                assert got_cs == -1.0
            else:
                assert got_cs == pytest.approx(want_cs)


def test_queue_push_pop_roundtrip():
    q = js.empty_edge_queue(4)
    q, ok = js.edge_push(q, 30.0, 0, 1.0, 30.0, 2)
    q, ok2 = js.edge_push(q, 10.0, 1, 1.0, 10.0, 1)
    assert bool(ok) and bool(ok2)
    q, idx, found = js.edge_pop_head(q)
    assert bool(found) and int(q.model[idx]) == 1   # earliest deadline first
    q, idx, found = js.edge_pop_head(q)
    assert bool(found) and int(q.model[idx]) == 2
    q, idx, found = js.edge_pop_head(q)
    assert not bool(found)


def test_queue_capacity_overflow_reports_failure():
    q = js.empty_edge_queue(2)
    for i in range(2):
        q, ok = js.edge_push(q, float(i), i, 1.0, 1.0, 0)
        assert bool(ok)
    q, ok = js.edge_push(q, 9.0, 9, 1.0, 1.0, 0)
    assert not bool(ok)


# ---------------------------------------------------------------------------
# one-hot forms of the per-arrival scatters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("mask", ["random", "all_false"])
def test_onehot_segment_matches_segment_ops(op, dtype, mask):
    """``onehot_segment`` is ``jax.ops.segment_sum``/``segment_max`` bit
    for bit, batched as in the fleet ``vmap``: out-of-range ids dropped,
    empty segments at the scatter's identity, padded models' +inf
    values kept."""
    import jax

    rng = np.random.default_rng(7)
    n, k, m_real, m = 16, 33, 6, 8
    ids = rng.integers(-1, m + 1, (n, k)).astype(np.int32)
    vals = rng.integers(-50, 50, (n, k)).astype(dtype)
    if dtype == "float32":
        vals[ids >= m_real] = np.inf          # padded models sit at +inf
    on = (rng.random((n, k)) < 0.5) if mask == "random" else np.zeros(
        (n, k), bool)
    # what a masked-off event contributes (the program's NEG for a max)
    if op == "sum":
        idle = 0
    elif dtype == "float32":
        idle = js.NEG
    else:
        idle = np.iinfo(np.int32).min + 1
    data = jnp.asarray(np.where(on, vals, idle).astype(dtype))
    seg = jax.ops.segment_sum if op == "sum" else jax.ops.segment_max
    want = jax.vmap(lambda d, i: seg(d, i, num_segments=m))(data, ids)
    got = jax.vmap(lambda d, i: js.onehot_segment(d, i, m, op))(data, ids)
    assert got.dtype == want.dtype
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def _edge_push_scatter(q, key, seq, t_edge, deadline, model, enable=True,
                       abs_dl=None):
    """``edge_push`` as a scalar-slot scatter, ``arr.at[slot].set``."""
    abs_dl = deadline if abs_dl is None else abs_dl
    free = ~q.valid
    slot = jnp.argmax(free)
    ok = free.any() & enable

    def set_at(arr, v):
        return jnp.where(ok, arr.at[slot].set(v), arr)
    return js.EdgeQueue(
        valid=set_at(q.valid, True), key=set_at(q.key, key),
        seq=set_at(q.seq, seq), t_edge=set_at(q.t_edge, t_edge),
        deadline=set_at(q.deadline, deadline),
        abs_dl=set_at(q.abs_dl, abs_dl), model=set_at(q.model, model),
    ), ok


@pytest.mark.parametrize("fill", ["empty", "partial", "full"])
@pytest.mark.parametrize("enable", [True, False])
def test_edge_push_one_hot_matches_scatter(fill, enable):
    """The one-hot ``edge_push`` writes the same slot and fields as the
    scatter form, on empty, partial and full queues, enabled or not."""
    import jax

    rng = np.random.default_rng(11)
    n = 8
    share = dict(empty=0.0, partial=0.5, full=1.0)[fill]
    q = js.EdgeQueue(
        valid=jnp.asarray(rng.random((n, CAP)) < share),
        key=jnp.asarray(rng.uniform(0, 900, (n, CAP)), jnp.float32),
        seq=jnp.asarray(rng.integers(0, 99, (n, CAP)), jnp.int32),
        t_edge=jnp.asarray(rng.uniform(0, 300, (n, CAP)), jnp.float32),
        deadline=jnp.asarray(rng.uniform(0, 900, (n, CAP)), jnp.float32),
        abs_dl=jnp.asarray(rng.uniform(0, 900, (n, CAP)), jnp.float32),
        model=jnp.asarray(rng.integers(0, M, (n, CAP)), jnp.int32))
    new = (jnp.asarray(rng.uniform(0, 900, n), jnp.float32),
           jnp.asarray(rng.integers(0, 99, n), jnp.int32),
           jnp.asarray(rng.uniform(0, 300, n), jnp.float32),
           jnp.asarray(rng.uniform(0, 900, n), jnp.float32),
           jnp.asarray(rng.integers(0, M, n), jnp.int32),
           jnp.full(n, enable),
           jnp.asarray(rng.uniform(0, 900, n), jnp.float32))
    got = jax.vmap(js.edge_push)(q, *new)
    want = jax.vmap(_edge_push_scatter)(q, *new)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
    if fill == "full" or not enable:
        assert not np.asarray(got[1]).any()
