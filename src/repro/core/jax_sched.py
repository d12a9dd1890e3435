"""The paper's scheduling decisions as vectorized JAX kernels.

The Python policies in :mod:`repro.core.schedulers` make O(queue-length)
decisions per task.  Here each decision is a fixed-shape masked ``jnp``
computation over array-encoded queues, so an entire *fleet* of edges can be
stepped with ``vmap`` and sharded with ``pjit`` (see
:mod:`repro.sim.fleet_jax`).  This is the TPU-native rethink of the paper's
control plane: the per-VIP scheduler becomes one SPMD program over the
city-scale deployment the paper targets in §8.6.

Queues are structure-of-arrays with a validity mask:

* edge queue:  ``valid, key, seq, t_edge, deadline, abs_dl, model`` —
  ``key`` is the policy priority (EDF: absolute deadline; HPF: negated
  utility-per-edge-second; SJF: execution time — see
  :func:`edge_priority_key`), ``seq`` breaks ties by insertion order
  (stable, like the list-based oracle), ``deadline`` is the *scheduling*
  deadline (SOTA1 may extend it by its 10 % buffer) and ``abs_dl`` the
  absolute one that decides success (they differ only under SOTA1).
* cloud queue: ``valid, trigger, t_edge, deadline, steal_only, rank``
  (cloud deadlines are always absolute — the oracle's ``abs_deadline``).

Every function is pure, shape-stable and differentiable-free; all are
property-tested against the discrete-event oracle in
``tests/test_jax_sched.py``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import sched_ops

NEG = -1e30
POS = 1e30


class EdgeQueue(NamedTuple):
    """Array-encoded edge priority queue (capacity = arrays' length)."""

    valid: jax.Array     # bool[Q]
    key: jax.Array       # f32[Q]  policy priority (see edge_priority_key)
    seq: jax.Array       # i32[Q]  insertion counter (stable tie-break)
    t_edge: jax.Array    # f32[Q]  expected edge latency t_i
    deadline: jax.Array  # f32[Q]  scheduling deadline (abs, + SOTA1 ext)
    abs_dl: jax.Array    # f32[Q]  absolute deadline t'_j + δ_i (success)
    model: jax.Array     # i32[Q]


class CloudQueue(NamedTuple):
    """Array-encoded trigger-time cloud queue (§5.3)."""

    valid: jax.Array       # bool[Qc]
    trigger: jax.Array     # f32[Qc]
    t_edge: jax.Array      # f32[Qc] expected *edge* latency (for stealing)
    deadline: jax.Array    # f32[Qc] absolute deadline
    steal_only: jax.Array  # bool[Qc] negative-cloud-utility parkees
    rank: jax.Array        # f32[Qc] (γ^E−γ^C)/t_i steal rank


def empty_edge_queue(capacity: int) -> EdgeQueue:
    z = jnp.zeros(capacity)
    return EdgeQueue(valid=jnp.zeros(capacity, bool), key=z, seq=jnp.zeros(
        capacity, jnp.int32), t_edge=z, deadline=z, abs_dl=z,
        model=jnp.zeros(capacity, jnp.int32))


def empty_cloud_queue(capacity: int) -> CloudQueue:
    z = jnp.zeros(capacity)
    return CloudQueue(valid=jnp.zeros(capacity, bool), trigger=z, t_edge=z,
                      deadline=z, steal_only=jnp.zeros(capacity, bool),
                      rank=z)


# ---------------------------------------------------------------------------
# ordering helpers
# ---------------------------------------------------------------------------

def _ahead_matrix(q: EdgeQueue) -> jax.Array:
    """``ahead[i, j]`` — valid task j sits ahead of task i in the queue.

    Priority order is (key, seq) lexicographic, matching the stable
    insertion of the list-based oracle.
    """
    ki, kj = q.key[:, None], q.key[None, :]
    si, sj = q.seq[:, None], q.seq[None, :]
    earlier = (kj < ki) | ((kj == ki) & (sj < si))
    return earlier & q.valid[None, :]


def ahead_of_new(q: EdgeQueue, new_key: jax.Array) -> jax.Array:
    """Mask of queued tasks ahead of a to-be-inserted task.

    New tasks are inserted *after* equal keys (stable), so everything with
    ``key <= new_key`` is ahead.
    """
    return q.valid & (q.key <= new_key)


def projected_completions(q: EdgeQueue, now: jax.Array,
                          busy_rem: jax.Array) -> jax.Array:
    """Projected completion time of every queued task (§5.2)."""
    ahead = _ahead_matrix(q)
    wait = (ahead * q.t_edge[None, :]).sum(-1)
    return now + busy_rem + wait + q.t_edge


# ---------------------------------------------------------------------------
# §5.1 / §8.2 — edge-queue priority keys
# ---------------------------------------------------------------------------

# runtime codes for PolicyParams.edge_prio (oracle Policy.edge_priority)
PRIO_EDF = 0   # "edf": absolute scheduling deadline t'_j + δ_i (§5.1)
PRIO_HPF = 1   # "hpf": highest utility-per-edge-second first (§8.2)
PRIO_SJF = 2   # "sjf": shortest job first (SJF-E+C / Dedas ordering)


def edge_priority_key(prio, sched_deadline, t_edge_eff,
                      gamma_e) -> jax.Array:
    """The oracle's ``Policy.edge_key`` as a runtime-selected scalar.

    Lower key = higher priority, ties broken by insertion ``seq``.
    ``t_edge_eff`` is the *effective* edge latency (speed factor folded
    in), matching the oracle, whose per-edge model tables fold the factor
    before ``hpf_rank``/SJF read ``t_edge``.
    """
    hpf = -gamma_e / t_edge_eff          # −γ^E/t_i: greedy utility rate
    return jnp.where(prio == PRIO_HPF, hpf,
                     jnp.where(prio == PRIO_SJF, t_edge_eff,
                               sched_deadline))


# ---------------------------------------------------------------------------
# §5.1 — EDF insertion feasibility
# ---------------------------------------------------------------------------

def insert_feasible(q: EdgeQueue, now, busy_rem, new_key, new_t_edge,
                    new_deadline) -> jax.Array:
    """Sum of execution times ahead + own ≤ deadline (paper §5.1)."""
    wait = jnp.where(ahead_of_new(q, new_key), q.t_edge, 0.0).sum()
    return now + busy_rem + wait + new_t_edge <= new_deadline


# ---------------------------------------------------------------------------
# §8.2 — SOTA2 (Dedas) average-completion-time comparison
# ---------------------------------------------------------------------------

def act_improves(q: EdgeQueue, now, busy_rem, new_key,
                 new_t_edge) -> jax.Array:
    """Dedas tie-break: does inserting keep the mean completion time down?

    Mirrors the oracle's ``_route_sota2`` ACT comparison for the
    exactly-one-violation case: the mean projected completion time over
    all queued tasks *with* the insert (tasks behind the new key shift by
    ``new_t_edge``; the new task completes after everything ahead of it)
    must not exceed the mean *without* it.  An empty queue compares
    against +inf, so the insert always "improves".
    """
    proj = projected_completions(q, now, busy_rem)
    ahead = ahead_of_new(q, new_key)
    behind = q.valid & ~ahead
    n = q.valid.sum()
    act_before = jnp.where(n > 0, jnp.where(q.valid, proj, 0.0).sum()
                           / jnp.maximum(n, 1), POS)
    new_proj = (now + busy_rem + jnp.where(ahead, q.t_edge, 0.0).sum()
                + new_t_edge)
    after_sum = (jnp.where(q.valid, proj, 0.0).sum()
                 + jnp.where(behind, new_t_edge, 0.0).sum() + new_proj)
    act_after = after_sum / (n + 1)
    return act_after <= act_before


# ---------------------------------------------------------------------------
# §5.2 — migration: victims and Eqn-3 scoring
# ---------------------------------------------------------------------------

def victim_mask(q: EdgeQueue, now, busy_rem, new_key,
                new_t_edge) -> jax.Array:
    """Tasks *newly* pushed past their deadline by inserting the new task."""
    proj = projected_completions(q, now, busy_rem)
    behind = q.valid & (q.key > new_key)
    return behind & (proj <= q.deadline) & (q.deadline < proj + new_t_edge)


def eqn3_scores(model_ids, now, deadlines, gamma_e, gamma_c,
                t_cloud_cur) -> jax.Array:
    """Vectorized Eqn 3: S = γ^E−γ^C if cloud-feasible ∧ γ^C>0 else γ^E."""
    ge = gamma_e[model_ids]
    gc = gamma_c[model_ids]
    feasible = now + t_cloud_cur[model_ids] <= deadlines
    return jnp.where(feasible & (gc > 0), ge - gc, ge)


def migration_decision(q: EdgeQueue, victims: jax.Array, now,
                       new_model, new_deadline, gamma_e, gamma_c,
                       t_cloud_cur) -> jax.Array:
    """True → insert new task, migrate victims; False → redirect new (§5.2)."""
    s_victims = jnp.where(
        victims, eqn3_scores(q.model, now, q.deadline, gamma_e, gamma_c,
                             t_cloud_cur), 0.0).sum()
    s_new = eqn3_scores(jnp.asarray(new_model)[None], now,
                        jnp.asarray(new_deadline)[None],
                        gamma_e, gamma_c, t_cloud_cur)[0]
    return s_victims < s_new


# ---------------------------------------------------------------------------
# §5.3 — work stealing
# ---------------------------------------------------------------------------

def max_front_delay(q: EdgeQueue, now, busy_rem) -> jax.Array:
    """Largest execution time insertable at the queue head without pushing
    any queued task past its deadline; +inf when the queue is empty."""
    proj = projected_completions(q, now, busy_rem)
    margins = jnp.where(q.valid, q.deadline - proj, POS)
    return margins.min()


def head_slack(q: EdgeQueue, now) -> jax.Array:
    """σ of the head task: (t'_j+δ_i) − (now + t_i); +inf if queue empty.

    Note the paper computes slack for the *head*, i.e. the task that would
    execute now, so busy_rem is zero by construction.
    """
    ahead = _ahead_matrix(q)
    is_head = q.valid & (ahead.sum(-1) == 0)
    slack = jnp.where(is_head, q.deadline - (now + q.t_edge), POS)
    return slack.min()


def steal_select(cq: CloudQueue, q: EdgeQueue, now, busy_rem,
                 min_edge_t) -> jax.Array:
    """Index of the cloud-queue task to steal, or −1 (§5.3).

    Eligibility: fits in the front-insertion margin, still edge-feasible.
    Preference: steal-only (negative cloud utility) tasks first, then by
    descending rank (γ^E−γ^C)/t_i.
    """
    any_queued = q.valid.any()
    slack = head_slack(q, now)
    delay_cap = jnp.where(any_queued, max_front_delay(q, now, busy_rem), POS)
    gate = jnp.where(any_queued, slack > min_edge_t, True)
    eligible = (cq.valid
                & (cq.t_edge <= delay_cap)
                & (now + cq.t_edge <= cq.deadline)
                & gate)
    # lexicographic (steal_only desc, rank desc) via a scalar score
    score = jnp.where(cq.steal_only, 1e12, 0.0) + cq.rank
    idx, _ = sched_ops.masked_argmax(score, eligible)
    return idx


# ---------------------------------------------------------------------------
# cross-edge peer offload (fleet-scope work stealing, beyond-paper)
# ---------------------------------------------------------------------------

def queue_load(q: EdgeQueue, busy_rem) -> jax.Array:
    """Total pending edge work: banked execution time + queued t_edge."""
    return jnp.maximum(busy_rem, 0.0) + jnp.where(q.valid, q.t_edge, 0.0).sum()


def queue_slacks(q: EdgeQueue, now, busy_rem) -> jax.Array:
    """Per-slot slack (deadline − projected completion); +inf for empties."""
    proj = projected_completions(q, now, busy_rem)
    return jnp.where(q.valid, q.deadline - proj, POS)


def export_select(q: EdgeQueue, now, busy_rem, dst_load,
                  slack_thresh) -> jax.Array:
    """Index of the task an overloaded edge should export, or −1.

    Candidates are queued tasks whose local slack is below
    ``slack_thresh`` (projected to miss, or nearly so) that would still be
    feasible appended behind the destination edge's current load.  The
    worst-slack candidate is exported first — the fleet-scope mirror of
    §5.3's "steal the task that needs rescue most".
    """
    slacks = queue_slacks(q, now, busy_rem)
    feasible_dst = now + dst_load + q.t_edge <= q.deadline
    cand = q.valid & feasible_dst & (slacks < slack_thresh)
    idx, _ = sched_ops.masked_argmin(slacks, cand)
    return idx


# ---------------------------------------------------------------------------
# §6 — GEMS window rescheduler (Alg. 1 lines 9–14)
# ---------------------------------------------------------------------------

def gems_reschedule_mask(q: EdgeQueue, now, lag_model, t_cloud_cur,
                         gamma_c) -> jax.Array:
    """Pending edge tasks of the lagging model to push to the cloud."""
    positive = gamma_c[lag_model] > 0
    feasible = now + t_cloud_cur[lag_model] <= q.deadline
    return q.valid & (q.model == lag_model) & feasible & positive


def window_update(lam, lam_hat, success) -> tuple[jax.Array, jax.Array,
                                                  jax.Array]:
    """Alg. 1 lines 3–7: increment counts, return the incremental rate."""
    lam = lam + 1
    lam_hat = lam_hat + success.astype(lam_hat.dtype)
    return lam, lam_hat, lam_hat / lam


def gems_winnable(lam, lam_hat, prev_lam, alpha, now, win_end,
                  window) -> jax.Array:
    """GEMS-B (beyond-paper): can α̂ still reach α this window?

    Vectorized mirror of the oracle's ``_WindowState.winnable``: the
    remaining arrivals are forecast from the *previous* window's count
    (``prev_lam``, prorated by the fraction of the window left); if even
    an all-success tail cannot lift the rate to α the window is
    mathematically lost and Alg. 1's rescheduling flood is pointless.
    """
    frac_left = jnp.clip((win_end - now) / window, 0.0, None)
    remaining = jnp.maximum(prev_lam, lam) * frac_left
    return lam_hat + remaining >= alpha * (lam + remaining) - 1e-9


# ---------------------------------------------------------------------------
# one-hot selects and segment reductions
# ---------------------------------------------------------------------------
# Under the fleet ``vmap`` an indexed update or per-edge lookup lowers to a
# batched scatter or gather, which the TPU applies one index at a time;
# a compare-select over a one-hot match runs in parallel instead.

def onehot_max(match: jax.Array, vals: jax.Array) -> jax.Array:
    """Per row ``i``, the largest ``vals[j]`` with ``match[i, j]``; the
    dtype's lowest value (False for bool) where none matches.

    With at most one True a row this is the lookup ``vals[j]``, bit for
    bit: a max passes -0.0 through, where a masked sum would give +0.0.
    """
    if vals.dtype == jnp.bool_:
        low = False
    elif jnp.issubdtype(vals.dtype, jnp.floating):
        low = -jnp.inf
    else:
        low = jnp.iinfo(vals.dtype).min
    return jnp.where(match, vals[None, :], low).max(-1)


def onehot_segment(data, segment_ids, num_segments: int,
                   op: str = "sum") -> jax.Array:
    """``jax.ops.segment_sum`` (``op="sum"``) or ``segment_max``
    (``op="max"``) as a reduction over an ``[M, K]`` one-hot match.

    The result is the scatter's: ids outside ``[0, num_segments)`` are
    dropped, an empty segment reads 0 (sum) or the dtype's lowest value
    (max), and integer sums and every max are exact (a float sum may
    round in another order).
    """
    data = jnp.asarray(data)
    hit = jnp.arange(num_segments)[:, None] == segment_ids[None, :]
    if op == "max":
        return onehot_max(hit, data)
    return jnp.where(hit, data[None, :], 0).sum(-1)


# ---------------------------------------------------------------------------
# §5.4 — DEMS-A adaptation
# ---------------------------------------------------------------------------

class AdaptState(NamedTuple):
    buf: jax.Array            # f32[M, w] circular buffers
    count: jax.Array          # i32[M] observations so far (≤ w)
    idx: jax.Array            # i32[M] next write slot
    current: jax.Array        # f32[M] current estimates t̂
    cooling_start: jax.Array  # f32[M]; −1 = not cooling


def adapt_init(static: jax.Array, w: int) -> AdaptState:
    m = static.shape[0]
    return AdaptState(buf=jnp.zeros((m, w)), count=jnp.zeros(m, jnp.int32),
                      idx=jnp.zeros(m, jnp.int32), current=static,
                      cooling_start=-jnp.ones(m))


def adapt_observe(st: AdaptState, model, obs, eps: float) -> AdaptState:
    """Mirror of ``AdaptiveEstimator.observe``: append until the buffer
    fills (write position = count), then overwrite circularly."""
    w = st.buf.shape[1]
    filling = st.count[model] < w
    write = jnp.where(filling, st.count[model], st.idx[model])
    buf = st.buf.at[model, write].set(obs)
    count = st.count.at[model].set(jnp.minimum(st.count[model] + 1, w))
    idx = st.idx.at[model].set(
        jnp.where(filling, st.idx[model], (st.idx[model] + 1) % w))
    n = count[model]
    avg = buf[model].sum() / n
    cur = st.current.at[model].set(
        jnp.where(avg - st.current[model] > eps, avg, st.current[model]))
    return AdaptState(buf, count, idx, cur, st.cooling_start)


def adapt_on_sent(st: AdaptState, model) -> AdaptState:
    return st._replace(cooling_start=st.cooling_start.at[model].set(-1.0))


def adapt_select(pred, a: AdaptState, b: AdaptState) -> AdaptState:
    """Elementwise ``where`` over whole estimator states (masked updates).

    The fleet tick loop computes a candidate post-event state for every
    queue slot and keeps it only where the event actually fired.
    """
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def adapt_on_skip(st: AdaptState, model, now, static, t_cp) -> AdaptState:
    inflated = st.current[model] > static[model]
    cs = st.cooling_start[model]
    expired = (cs >= 0) & (now - cs >= t_cp)
    new_cur = jnp.where(inflated & expired, static[model], st.current[model])
    new_cs = jnp.where(~inflated, cs,
                       jnp.where(expired, -1.0, jnp.where(cs < 0, now, cs)))
    return AdaptState(st.buf, st.count, st.idx,
                      st.current.at[model].set(new_cur),
                      st.cooling_start.at[model].set(new_cs))


def adapt_feed_batch(st: AdaptState, model_ids, sent, obs, obs_val, skip,
                     now, static, eps, t_cp, *, with_obs: bool = True,
                     max_obs: int | None = None) -> AdaptState:
    """One batched estimator update for a whole tick's worth of events.

    Replaces the per-queue-slot ``fori_loop`` of
    ``on_sent``/``observe``/``on_skip`` calls with masked array updates:
    per model, every ``sent`` cooling reset applies, then all ``obs``
    observations land in slot order (their values must be equal within
    one call — true in the fleet tick, where a model's actual duration is
    a function of (model, tick) only), then at most one ``skip``
    (same-instant repeated skips are idempotent).

    **Event-ordering caveat (sends-then-skips).**  A model that both
    dispatches *and* skips in the same tick diverges from the sequential
    slot loop: the loop interleaves events in queue-slot order (a skip in
    slot 2 lands *before* a send in slot 5), whereas this batch applies
    all sends first, then the skips.  The divergence is confined to the
    cooling timer: a slot-ordered ``skip → send`` pair starts cooling and
    immediately clears it (net no-op), while the batch's ``send → skip``
    leaves the model cooling from ``now``.  Both orders agree again at
    the next dispatch (any send clears the timer), so the visible effect
    is bounded to at most one cooling window ``t_cp`` *starting* a few
    slots early — it can only make the §5.4 point-of-no-return reset
    fire sooner, never later, and only for models mixing sends and skips
    within one ``dt``.  No registry scenario exercises this (a tick's
    dispatch gate is feasibility-monotone per model: same-model entries
    share one t̂, so they skip together or send together; mixes need a
    deadline straddle within a single tick).  If a future scenario makes
    the interleave matter, thread each event's queue-slot index into this
    call and fold it into the per-model segment reductions (order the
    replay tensors by slot instead of assuming sends-first) — the same
    batched-per-tick simplification :mod:`repro.sim.fleet_jax` documents
    for DEMS-A.

    With all masks False the state is returned bit-identical, so callers
    gate adaptivity by AND-ing a runtime policy flag into the masks.
    ``with_obs=False`` skips building the observation tensors for
    skip-only call sites (rejected cloud offers).  ``max_obs`` promises
    that no model observes more than that many times in this call (e.g.
    the finite pool depth — one tick cannot dispatch more tasks than it
    has free slots); it bounds the ``[M, j, w]`` replay tensors and the
    ratchet, the hottest per-tick allocation.
    """
    m, w = st.buf.shape
    k = model_ids.shape[0]
    cnt = onehot_segment(obs.astype(jnp.int32), model_ids, m)     # i32[M]
    cs = jnp.where(
        onehot_segment(sent.astype(jnp.int32), model_ids, m) > 0,
        -1.0, st.cooling_start)
    cur, buf, count, idx = st.current, st.buf, st.count, st.idx
    if with_obs:
        jmax = k if max_obs is None else min(k, max_obs)
        v = onehot_segment(jnp.where(obs, obs_val, NEG), model_ids, m,
                           "max")                                 # f32[M]
        j = jnp.arange(jmax)[None, :]                             # [1,J]
        fill = jnp.clip(w - count, 0, None)[:, None]              # [M,1]
        # the j-th observation of model m writes slot: fill positions
        # count..w-1 first, then wrap circularly from idx (the exact
        # write path of adapt_observe, iterated)
        pos = jnp.where(j < fill, count[:, None] + j,
                        (idx[:, None] + j - fill) % w)            # [M,J]
        active = j < cnt[:, None]
        onehot = active[:, :, None] & (
            pos[:, :, None] == jnp.arange(w)[None, None, :])      # [M,J,w]
        written_upto = jnp.cumsum(onehot, axis=1) > 0
        buf = jnp.where(written_upto[:, -1, :], v[:, None], buf)
        # the current-estimate ratchet is path-dependent (an average only
        # sticks when it clears cur+eps), so replay the per-observation
        # averages — but as J tiny [M]-wide steps, not K full-state scans
        sums = st.buf.sum(-1)[:, None] + jnp.where(
            written_upto, v[:, None, None] - st.buf[:, None, :],
            0.0).sum(-1)                                          # [M,J]
        nobs = jnp.minimum(count[:, None] + 1 + jnp.arange(jmax)[None, :],
                           w)
        avgs = sums / nobs

        def ratchet(jj, c):
            a = avgs[:, jj]
            return jnp.where((jj < cnt) & (a - c > eps), a, c)

        cur = jax.lax.fori_loop(0, jmax, ratchet, cur)
        count = jnp.minimum(st.count + cnt, w)
        idx = (st.idx + (cnt - jnp.clip(w - st.count, 0, cnt))) % w
    any_skip = onehot_segment(skip.astype(jnp.int32), model_ids, m) > 0
    inflated = cur > static
    expired = (cs >= 0) & (now - cs >= t_cp)
    new_cur = jnp.where(any_skip & inflated & expired, static, cur)
    new_cs = jnp.where(
        any_skip,
        jnp.where(~inflated, cs,
                  jnp.where(expired, -1.0, jnp.where(cs < 0, now, cs))),
        cs)
    return AdaptState(buf, count, idx, new_cur, new_cs)


# ---------------------------------------------------------------------------
# queue mutation helpers (used by the fleet simulator)
# ---------------------------------------------------------------------------

def edge_push(q: EdgeQueue, key, seq, t_edge, deadline, model,
              enable=True, abs_dl=None) -> tuple[EdgeQueue, jax.Array]:
    """Insert into the first free slot; returns (queue, ok).

    ``abs_dl`` is the absolute deadline deciding success; it defaults to
    ``deadline`` (they differ only under SOTA1's scheduling extension).
    """
    abs_dl = deadline if abs_dl is None else abs_dl
    free = ~q.valid
    ok = free.any() & enable
    # a one-hot select, not ``arr.at[slot].set``: no scatter under vmap
    at = (jnp.arange(free.shape[0]) == jnp.argmax(free)) & ok
    def set_at(arr, v):
        return jnp.where(at, jnp.asarray(v).astype(arr.dtype), arr)
    return EdgeQueue(
        valid=set_at(q.valid, True), key=set_at(q.key, key),
        seq=set_at(q.seq, seq), t_edge=set_at(q.t_edge, t_edge),
        deadline=set_at(q.deadline, deadline),
        abs_dl=set_at(q.abs_dl, abs_dl), model=set_at(q.model, model),
    ), ok


def edge_pop_head(q: EdgeQueue) -> tuple[EdgeQueue, jax.Array, jax.Array]:
    """Remove and return the head (index, found) by (key, seq) order."""
    ahead = _ahead_matrix(q)
    is_head = q.valid & (ahead.sum(-1) == 0)
    idx = jnp.argmax(is_head)
    found = is_head.any()
    return q._replace(valid=jnp.where(found, q.valid.at[idx].set(False),
                                      q.valid)), idx, found


def edge_remove(q: EdgeQueue, mask: jax.Array) -> EdgeQueue:
    return q._replace(valid=q.valid & ~mask)


def cloud_push(cq: CloudQueue, trigger, t_edge, deadline, steal_only,
               rank, enable=True) -> tuple[CloudQueue, jax.Array]:
    free = ~cq.valid
    slot = jnp.argmax(free)
    ok = free.any() & enable
    def set_at(arr, v):
        return jnp.where(ok, arr.at[slot].set(v), arr)
    return CloudQueue(
        valid=set_at(cq.valid, True), trigger=set_at(cq.trigger, trigger),
        t_edge=set_at(cq.t_edge, t_edge),
        deadline=set_at(cq.deadline, deadline),
        steal_only=set_at(cq.steal_only, steal_only),
        rank=set_at(cq.rank, rank)), ok


def cloud_remove(cq: CloudQueue, idx) -> CloudQueue:
    return cq._replace(valid=cq.valid.at[idx].set(False))
