"""Plain reference of the fleet tick: DEMS-A, and DEMS-A with peer offload.

Straightforward NumPy over a leading edge axis, written from the fleet
model's stated semantics (the paper's §5.1-5.4 rules as the fleet
simulator applies them once per ``dt`` tick) and importing nothing of the
system under test.  It keeps the same per-tick order of phases:

1. cloud resolve: matured cloud-queue tasks are JIT-checked against the
   adapted estimate, dispatched into free FaaS slots in queue-slot order,
   and settle at dispatch (success iff they finish by their deadline);
2. routing: the tick's arrivals are inserted one by one in the tick's
   randomised order, EDF with the insertion-feasibility check and §5.2
   migration of the tasks the insert would push past their deadline;
   victims and redirected arrivals are offered to the trigger-time cloud
   queue in one batch that reads the tick's pre-offer queue state;
3. edge execute: up to ``substeps`` actions, each a JIT drop of an
   infeasible head, or a start (a §5.3 steal first, else the head);
4. between ticks, with cooperation, ``coop_rounds`` peer transfers of the
   worst-slack exportable task to the least-loaded other edge.

Edges may be stacked from independent runs: ``slots`` gives each edge its
own FaaS slot count, and ``groups`` each edge its run, so the peer
exchange stays inside a run (the runs are contiguous blocks of edges of
one size).

Floats are held in ``dtype`` (float32, as the configuration states; the
control passes bfloat16).  Queue times are whole milliseconds at nominal
edge speed, so sums over a queue are exact in any order; every
non-integer expression keeps the evaluation order the fleet model
documents.  Only the DEMS-A flag set is implemented; a policy reaches it
through its own file under ``bench/refs/`` (:func:`harness.check.reference`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

POS = 1e30
NEG = -1e30

# per-tick, fleet-summed decision counters (the live controller's records)
COUNTERS = ("arrivals", "admit_edge", "admit_cloud", "migrated",
            "cloud_dispatch", "pool_blocked", "gems_moved", "edge_exec",
            "peer_out", "peer_in", "drop_infeasible", "drop_unstolen",
            "drop_qfull", "drop_crash", "drop_timeout")
OUTCOMES = ("hit", "miss", "drop", "stolen")


@dataclasses.dataclass(frozen=True)
class Params:
    """Scheduler constants, from a configuration file's ``scheduler``."""

    dt: float
    edge_frac: float
    cloud_frac: float
    edge_cap: int
    cloud_cap: int
    substeps: int
    cloud_slots: int
    cloud_margin: float
    adapt_window: int
    adapt_eps: float
    adapt_cooling_ms: float
    segment_kb: float
    nominal_bw_mbps: float
    cooperation: bool = False
    coop_slack_ms: float = 0.0
    coop_rounds: int = 0

    @classmethod
    def from_config(cls, cfg: dict, coop: bool) -> "Params":
        s = cfg["scheduler"]
        return cls(dt=s["dt_ms"], edge_frac=s["edge_frac"],
                   cloud_frac=s["cloud_frac"], edge_cap=s["edge_queue_cap"],
                   cloud_cap=s["cloud_queue_cap"], substeps=s["substeps"],
                   cloud_slots=s["cloud_slots"],
                   cloud_margin=s["cloud_margin_ms"],
                   adapt_window=s["adapt_window"],
                   adapt_eps=s["adapt_eps_ms"],
                   adapt_cooling_ms=s["adapt_cooling_ms"],
                   segment_kb=s["segment_kb"],
                   nominal_bw_mbps=s["nominal_bw_mbps"],
                   cooperation=coop, coop_slack_ms=s["coop_slack_ms"],
                   coop_rounds=s["coop_max_transfers"] if coop else 0)


class Table:
    """The model table (paper Table 1 rows) in the reference's dtype."""

    def __init__(self, rows: list[dict], dtype):
        def f(key):
            return np.asarray([r[key] for r in rows], np.float64)
        beta, k_e, k_c = f("beta"), f("cost_edge"), f("cost_cloud")
        t_edge = f("t_edge_ms")
        self.names = [r["name"] for r in rows]
        self.t_edge = t_edge.astype(dtype)
        self.t_cloud = f("t_cloud_ms").astype(dtype)
        self.deadline = f("deadline_ms").astype(dtype)
        self.gamma_e = (beta - k_e).astype(dtype)         # γ^E = β − K
        self.gamma_c = (beta - k_c).astype(dtype)         # γ^C = β − K̂
        self.cost_e = k_e.astype(dtype)
        self.cost_c = k_c.astype(dtype)
        # §5.3 steal rank (γ^E − γ^C) / t_i
        self.steal_rank = (((beta - k_e) - (beta - k_c)) / t_edge
                           ).astype(dtype)


def _excl_cumsum(mask: np.ndarray) -> np.ndarray:
    m = mask.astype(np.int32)
    return np.cumsum(m, axis=-1, dtype=np.int32) - m


def _take(a: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """``np.take_along_axis(a, idx, -1)`` for 2-D ``a`` and ``idx``."""
    return a[np.arange(a.shape[0])[:, None], idx]


def _first_true(mask: np.ndarray) -> np.ndarray:
    """Index of the first True per row (0 for a row with none)."""
    return np.argmax(mask, axis=-1)


def _masked_arg(scores, mask, is_max: bool):
    """First arg-extremum over enabled entries, per row of a 2-D array;
    -1 where none."""
    fill = NEG if is_max else POS
    v = np.where(mask, scores, scores.dtype.type(fill))
    idx = np.argmax(v, -1) if is_max else np.argmin(v, -1)
    best = _take(v, idx[:, None])[:, 0]
    return np.where(mask.any(-1), idx, -1), best


_STATE = ("eq_valid", "eq_key", "eq_seq", "eq_te", "eq_dl", "eq_abs",
          "eq_model", "cq_valid", "cq_trig", "cq_te", "cq_dl", "cq_so",
          "cq_rank", "cq_model", "cq_blocked", "busy_rem", "busy_until",
          "seq", "n_success", "n_miss", "n_drop", "n_stolen", "n_edge_exec",
          "qos", "n_peer_out", "n_peer_in", "a_buf", "a_count", "a_idx",
          "a_cur", "a_cool", "n_slots")


def _group_size(groups, n_edges: int) -> int:
    """Edges per run for per-edge run ids ``groups`` (``None``: one run of
    every edge); runs must be contiguous blocks of one size."""
    if groups is None:
        return max(n_edges, 1)
    groups = np.asarray(groups)
    _, sizes = np.unique(groups, return_counts=True)
    g = int(sizes[0])
    if (len(groups) != n_edges or np.any(sizes != g)
            or np.any(groups != np.repeat(groups[::g], g))):
        raise ValueError("groups must be contiguous runs of equal size")
    return g


class FleetRef:
    """Stacked state of ``n_edges`` edges, stepped one tick at a time.

    ``slots`` (per edge, default the configuration's ``cloud_slots``) is
    each edge's FaaS slot count; ``groups`` (per edge) the run each edge
    belongs to, for the peer exchange (default: one run)."""

    def __init__(self, table: Table, p: Params, n_edges: int,
                 dtype=np.float32, slots=None, groups=None):
        self.tb, self.p, self.ft = table, p, dtype
        e, q, c, m = n_edges, p.edge_cap, p.cloud_cap, len(table.names)
        w = p.adapt_window
        n_slots = np.broadcast_to(
            np.asarray(p.cloud_slots if slots is None else slots, np.int32),
            (e,)).copy()
        s = int(n_slots.max(initial=1))
        self.group = _group_size(groups, e)
        f = lambda *shape: np.zeros(shape, dtype)  # noqa: E731
        i = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
        b = lambda *shape: np.zeros(shape, bool)  # noqa: E731
        self.E, self.M = e, m
        # edge queue (EDF by key = absolute deadline, ties by insertion seq)
        self.eq_valid, self.eq_key, self.eq_seq = b(e, q), f(e, q), i(e, q)
        self.eq_te, self.eq_dl, self.eq_abs = f(e, q), f(e, q), f(e, q)
        self.eq_model = i(e, q)
        # trigger-time cloud queue
        self.cq_valid, self.cq_trig, self.cq_te = b(e, c), f(e, c), f(e, c)
        self.cq_dl, self.cq_so, self.cq_rank = f(e, c), b(e, c), f(e, c)
        self.cq_model, self.cq_blocked = i(e, c), b(e, c)
        self.busy_rem = f(e)
        # FaaS slots, free iff <= now; an edge's slots past its own count
        # are never free
        self.n_slots = n_slots
        self.busy_until = np.where(np.arange(s) < n_slots[:, None],
                                   self.c(0), self.c(POS)).astype(dtype)
        self.seq = i(e)
        self.n_success, self.n_miss, self.n_drop = i(e, m), i(e, m), i(e, m)
        self.n_stolen, self.n_edge_exec = i(e, m), i(e, m)
        self.qos = f(e)
        self.n_peer_out, self.n_peer_in = i(e), i(e)
        # DEMS-A sliding-window estimator, per edge and model
        self.a_buf, self.a_count, self.a_idx = f(e, m, w), i(e, m), i(e, m)
        self.a_cur = np.broadcast_to(table.t_cloud, (e, m)).astype(dtype)
        self.a_cool = np.full((e, m), -1.0, dtype)
        self.counters: dict[str, np.ndarray] = {}

    # -- helpers ---------------------------------------------------------
    def c(self, x) -> np.ndarray:
        """A constant in the reference's dtype."""
        return np.asarray(x, self.ft)

    def _count(self, mask: np.ndarray, model: np.ndarray) -> np.ndarray:
        """Per-edge, per-model count of a slot mask: ``[E, M]``."""
        e = np.broadcast_to(np.arange(self.E)[:, None], mask.shape)
        flat = e[mask] * self.M + model[mask]
        return np.bincount(flat, minlength=self.E * self.M).reshape(
            self.E, self.M).astype(np.int32)

    def _rows(self, rows: np.ndarray) -> "FleetRef":
        """The edges ``rows`` as a fleet of their own (state copied); the
        phases that leave an edge unchanged where it has no work run on
        the edges that have some, and :meth:`_put_rows` writes them back."""
        sub = object.__new__(FleetRef)
        sub.tb, sub.p, sub.ft, sub.M = self.tb, self.p, self.ft, self.M
        sub.group = self.group
        sub.E, sub.counters = len(rows), self.counters
        for f in _STATE:
            setattr(sub, f, getattr(self, f)[rows])
        return sub

    def _put_rows(self, rows: np.ndarray, sub: "FleetRef") -> None:
        for f in _STATE:
            getattr(self, f)[rows] = getattr(sub, f)

    def _add(self, name: str, v) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(np.sum(v))

    def _queue_order(self):
        """Projected completion and head of every edge queue (§5.2).

        Queue order is (key, seq); a task's projected completion is
        ``now + busy + (t_edge of the tasks ahead) + its own t_edge``.
        Returns ``(wait, is_head)`` with ``wait`` the sum ahead.
        """
        v = self.eq_valid
        # ordering only: widening the key to float32 is exact
        perm = np.lexsort((self.eq_seq, self.eq_key.astype(np.float32), ~v),
                          axis=-1)
        te_sorted = _take(np.where(v, self.eq_te, self.c(0)), perm)
        ahead_sorted = np.cumsum(te_sorted, axis=-1, dtype=self.ft) \
            - te_sorted
        wait = np.zeros_like(ahead_sorted)
        wait[np.arange(len(perm))[:, None], perm] = ahead_sorted
        head = perm[:, 0]
        is_head = np.zeros_like(v)
        is_head[np.arange(len(v)), head] = v.any(-1)
        return wait, is_head

    def _proj(self, now, busy):
        wait, is_head = self._queue_order()
        return now + busy[:, None] + wait + self.eq_te, is_head

    def _pool_wait(self, now):
        pending = (self.cq_valid & ~self.cq_so).sum(-1)
        k = np.clip(pending, 0, self.n_slots - 1)
        kth = _take(np.sort(self.busy_until, -1), k[:, None])[:, 0]
        return np.maximum(kth - now, self.c(0))

    def _t_cur(self, now):
        return self.a_cur + self._pool_wait(now)[:, None]

    def _adapt(self, model, sent, obs, obs_val, skip, now, with_obs=True):
        """The estimator's events of one batch, per edge and model: every
        send clears the cooling timer, then the observations land one by
        one (each may raise t̂ to the window average when it exceeds t̂ by
        more than eps), then at most one skip (§5.4)."""
        if not (sent.any() or skip.any() or (with_obs and obs.any())):
            return
        m, w = self.M, self.p.adapt_window
        cool = np.where(self._count(sent, model) > 0, self.c(-1), self.a_cool)
        cur = self.a_cur
        if with_obs:
            cnt = self._count(obs, model)
            onehot = obs[:, :, None] & (model[:, :, None] == np.arange(m))
            v = np.where(onehot, obs_val[:, :, None], self.c(NEG)).max(1)
            buf, count, idx = (self.a_buf.copy(), self.a_count.copy(),
                               self.a_idx.copy())
            cur = cur.copy()
            for j in range(int(cnt.max(initial=0))):
                on = j < cnt                                   # [E, M]
                filling = count < w
                pos = np.where(filling, count, idx)
                hot = on[:, :, None] & (pos[:, :, None] == np.arange(w))
                buf = np.where(hot, v[:, :, None], buf)
                count = np.where(on, np.minimum(count + 1, w), count)
                idx = np.where(on & ~filling, (idx + 1) % w, idx)
                avg = buf.sum(-1, dtype=self.ft) / np.maximum(count, 1).astype(
                    self.ft)
                cur = np.where(on & (avg - cur > self.c(self.p.adapt_eps)),
                               avg, cur)
            self.a_buf, self.a_count, self.a_idx = buf, count, idx
        any_skip = self._count(skip, model) > 0
        static = self.tb.t_cloud[None, :]
        inflated = cur > static
        expired = (cool >= 0) & (now - cool >= self.c(self.p.adapt_cooling_ms))
        self.a_cur = np.where(any_skip & inflated & expired, static, cur)
        self.a_cool = np.where(
            any_skip, np.where(~inflated, cool, np.where(
                expired, self.c(-1), np.where(cool < 0, now, cool))), cool)

    def _occupy(self, now, dispatch, end):
        """Dispatched task k (in slot order) takes the k-th free slot."""
        s = self.busy_until.shape[1]
        drank = _excl_cumsum(dispatch)
        by_rank = np.zeros((self.E, s + 1), self.ft)
        e_i, c_i = np.nonzero(dispatch)
        by_rank[e_i, drank[e_i, c_i]] = end[e_i, c_i]
        free = self.busy_until <= now
        frank = _excl_cumsum(free)
        fill = free & (frank < dispatch.sum(-1, keepdims=True))
        got = _take(by_rank, np.minimum(frank, s))
        self.busy_until = np.where(fill, got, self.busy_until)

    def _free_gate(self, now, want):
        taken_before = _excl_cumsum(want)
        return taken_before < (self.busy_until <= now).sum(-1, keepdims=True)

    # -- phases ----------------------------------------------------------
    def _resolve_cloud(self, now, theta, bw_pen, cloud_up, link_up, jit_c):
        tb, p, mdl = self.tb, self.p, self.cq_model
        mature = (self.cq_valid & (self.cq_trig <= now)
                  & cloud_up[:, None] & link_up[:, None])
        run = mature & ~self.cq_so
        fits = now + _take(self.a_cur, mdl) <= self.cq_dl
        avail = self._free_gate(now, run & fits)
        dispatch = run & fits & avail
        skipped = run & ~fits & avail
        act = (self.c(p.cloud_frac) * tb.t_cloud[mdl]
               * _take(jit_c, mdl) + theta[:, None]
               + bw_pen[:, None])
        success = dispatch & (now + act <= self.cq_dl)
        util = np.where(success, tb.gamma_c[mdl],
                        np.where(dispatch, -tb.cost_c[mdl], self.c(0)))
        self.n_success += self._count(success, mdl)
        self.n_miss += self._count(dispatch & ~success, mdl)
        dropped = mature & self.cq_so          # steal-only, not stolen
        self.n_drop += self._count(dropped | skipped, mdl)
        self._add("cloud_dispatch", dispatch)
        self._add("pool_blocked", run & ~avail)
        self._add("drop_infeasible", skipped)
        self._add("drop_unstolen", dropped)
        settled = dispatch | skipped | dropped
        self.cq_valid = self.cq_valid & ~settled
        self._occupy(now, dispatch, now + act)
        self.cq_blocked = (self.cq_blocked | (run & ~avail)) & self.cq_valid
        self.qos = self.qos + util.sum(-1, dtype=self.ft)
        self._adapt(mdl, dispatch, dispatch, act, skipped, now)

    def _offer_cloud(self, now, models, dls, tes, offer, t_cur):
        """Admit a batch of cloud offers against the pre-offer state; the
        accepted fill the free cloud-queue slots in ascending order."""
        tb, p = self.tb, self.p
        t_hat = _take(t_cur, models)
        feasible = now + t_hat <= dls
        negative = tb.gamma_c[models] <= 0
        trig = np.where(negative, dls - tes,
                        np.maximum(now, dls - t_hat - self.c(p.cloud_margin)))
        accept = offer & feasible & np.where(negative, trig >= now, True)
        free = ~self.cq_valid
        qc = free.shape[1]
        arank = _excl_cumsum(accept)
        pushed = accept & (arank < free.sum(-1, keepdims=True))
        frank = _excl_cumsum(free)
        fill = free & (frank < pushed.sum(-1, keepdims=True))
        e_i, k_i = np.nonzero(pushed)

        def put(old, vals):
            by_rank = np.zeros((self.E, qc + 1), old.dtype)
            by_rank[e_i, arank[e_i, k_i]] = vals[e_i, k_i]
            got = _take(by_rank, np.minimum(frank, qc))
            return np.where(fill, got, old)

        self.cq_trig = put(self.cq_trig, trig)
        self.cq_te = put(self.cq_te, tes)
        self.cq_dl = put(self.cq_dl, dls)
        self.cq_so = put(self.cq_so, negative)
        self.cq_rank = put(self.cq_rank, tb.steal_rank[models])
        self.cq_model = put(self.cq_model, models)
        self.cq_valid = self.cq_valid | fill
        self.cq_blocked = self.cq_blocked & ~fill
        none = np.zeros_like(offer)
        self._adapt(models, none, none, None, offer & ~accept, now,
                    with_obs=False)
        return pushed, accept

    def _route(self, now, mdl, arrive, load_mult, edge_up):
        """One arrival per edge (model ``mdl[e]``, present iff ``arrive``)."""
        tb = self.tb
        abs_dl = now + tb.deadline[mdl]
        te = tb.t_edge[mdl] * load_mult
        key = abs_dl                                        # EDF
        v = self.eq_valid
        wait_new = np.where(v & (self.eq_key <= key[:, None]), self.eq_te,
                            self.c(0)).sum(-1, dtype=self.ft)
        feas = now + self.busy_rem + wait_new + te <= abs_dl
        proj, _ = self._proj(now, self.busy_rem)
        behind = v & (self.eq_key > key[:, None])
        victims = behind & (proj <= self.eq_dl) & (self.eq_dl
                                                   < proj + te[:, None])
        t_cur = self._t_cur(now)

        def eqn3(models, dls):                               # Eqn 3
            ge, gc = tb.gamma_e[models], tb.gamma_c[models]
            cf = now + _take(t_cur, models) <= dls
            return np.where(cf & (gc > 0), ge - gc, ge)

        s_vic = np.where(victims, eqn3(self.eq_model, self.eq_dl),
                         self.c(0)).sum(-1, dtype=self.ft)
        s_new = eqn3(mdl[:, None], abs_dl[:, None])[:, 0]
        edge_ok = feas & (~victims.any(-1) | (s_vic < s_new))
        insert = arrive & edge_ok & edge_up
        vic = victims & insert[:, None]
        to_cloud = arrive & ~insert
        models = np.concatenate([self.eq_model, mdl[:, None]], 1)
        dls = np.concatenate([self.eq_abs, abs_dl[:, None]], 1)
        tes = np.concatenate([self.eq_te, te[:, None]], 1)
        offer = np.concatenate([vic, to_cloud[:, None]], 1)
        pushed, accepted = self._offer_cloud(now, models, dls, tes, offer,
                                             t_cur)
        self.eq_valid = self.eq_valid & ~vic
        free = ~self.eq_valid
        slot = _first_true(free)
        ok = free.any(-1) & insert
        rows = np.nonzero(ok)[0]
        at = (rows, slot[rows])
        self.eq_valid[at] = True
        self.eq_key[at] = key[rows]
        self.eq_seq[at] = self.seq[rows]
        self.eq_te[at] = te[rows]
        self.eq_dl[at] = abs_dl[rows]
        self.eq_abs[at] = abs_dl[rows]
        self.eq_model[at] = mdl[rows]
        lost = insert & ~ok
        self._add("arrivals", arrive)
        self._add("admit_edge", insert & ok)
        self._add("admit_cloud", pushed)
        self._add("migrated", vic)
        self._add("drop_infeasible", offer & ~accepted)
        self._add("drop_qfull", lost)
        self._add("drop_qfull", offer & accepted & ~pushed)
        self.seq = self.seq + arrive.astype(np.int32)
        self.n_drop[np.arange(self.E), mdl] += lost.astype(np.int32)
        self.n_drop += self._count(offer & ~pushed, models)

    def _execute(self, now, jit_e, edge_up):
        flush = self.eq_valid & ~edge_up[:, None]
        self.n_drop += self._count(flush, self.eq_model)
        self.eq_valid = self.eq_valid & ~flush
        self._add("drop_crash", flush)
        # an edge still busy, or with no task queued, does nothing in a
        # substep; nor does one that did nothing in the substep before
        acting = (self.eq_valid.any(-1) | self.cq_valid.any(-1))
        for _ in range(self.p.substeps):
            rows = np.nonzero(acting & (self.busy_rem <= 0))[0]
            if not len(rows):
                break
            sub = self._rows(rows)
            acting[rows] = sub._substep(now, jit_e[rows], edge_up[rows])
            self._put_rows(rows, sub)
        dt = self.c(self.p.dt)
        self.busy_rem = np.maximum(self.busy_rem - dt, -dt)

    def _substep(self, now, jit_e, edge_up):
        """One executor action per edge: a JIT drop of an infeasible head,
        or a start (a §5.3 steal first, else the head).  Returns the edges
        that acted."""
        tb, p = self.tb, self.p
        rows = np.arange(self.E)
        min_edge_t = tb.t_edge.min()
        idle = self.busy_rem <= 0
        _, is_head = self._queue_order()
        found = is_head.any(-1)
        h = _first_true(is_head)
        infeasible = found & (now + self.eq_te[rows, h]
                              > self.eq_dl[rows, h])
        drop = idle & infeasible
        self.eq_valid[rows[drop], h[drop]] = False
        self.n_drop[rows, self.eq_model[rows, h]] += drop.astype(np.int32)
        self._add("drop_infeasible", drop)
        idle = idle & ~infeasible
        # §5.3 stealing: a cloud-queued task that fits at the head
        busy = np.maximum(self.busy_rem, self.c(0))
        proj, is_head = self._proj(now, busy)
        queued = self.eq_valid.any(-1)
        head_slack = np.where(is_head, self.eq_dl - (now + self.eq_te),
                              self.c(POS)).min(-1)
        margin = np.where(self.eq_valid, self.eq_dl - proj,
                          self.c(POS)).min(-1)
        cap = np.where(queued, margin, self.c(POS))
        gate = np.where(queued, head_slack > min_edge_t, True)
        eligible = (self.cq_valid & (self.cq_te <= cap[:, None])
                    & (now + self.cq_te <= self.cq_dl) & gate[:, None])
        score = np.where(self.cq_so, self.c(1e12), self.c(0)) \
            + self.cq_rank
        sidx, _ = _masked_arg(score, eligible, is_max=True)
        steal = idle & (sidx >= 0) & edge_up
        si = np.maximum(sidx, 0)
        smodel = self.cq_model[rows, si]
        self.cq_valid[rows[steal], si[steal]] = False
        self.n_stolen[rows, smodel] += steal.astype(np.int32)
        # start: the stolen task, else the head
        found = is_head.any(-1)
        h = _first_true(is_head)
        start_head = idle & ~steal & found
        run_model = np.where(steal, smodel, self.eq_model[rows, h])
        run_dl = np.where(steal, self.cq_dl[rows, si],
                          self.eq_abs[rows, h])
        run_te = np.where(steal, self.cq_te[rows, si],
                          self.eq_te[rows, h])
        start = steal | start_head
        act = self.c(p.edge_frac) * run_te * jit_e[rows, run_model]
        success = start & (now + act <= run_dl)
        util = np.where(success, tb.gamma_e[run_model],
                        np.where(start, -tb.cost_e[run_model],
                                 self.c(0)))
        self.eq_valid[rows[start_head], h[start_head]] = False
        self.busy_rem = np.where(start, self.busy_rem + act,
                                 self.busy_rem)
        self.n_success[rows, run_model] += success.astype(np.int32)
        self.n_edge_exec[rows, run_model] += start.astype(np.int32)
        self.n_miss[rows, run_model] += (start & ~success).astype(
            np.int32)
        self.qos = self.qos + util
        self._add("edge_exec", start)
        return drop | start

    def _peer_offload(self, now, edge_valid):
        """Between ticks: move the worst-slack exportable task of the most
        overloaded edge to the least-loaded other edge of its run, per
        round and per run."""
        p, g = self.p, self.group
        n = self.E // g
        e = np.arange(g)                          # an edge's place in its run
        base = np.arange(n) * g
        valid = edge_valid.reshape(n, g)
        thresh = self.c(p.coop_slack_ms)
        for _ in range(p.coop_rounds):
            busy = np.maximum(self.busy_rem, self.c(0))
            proj, _ = self._proj(now, busy)
            slacks = np.where(self.eq_valid, self.eq_dl - proj, self.c(POS))
            min_slack = np.where(edge_valid, slacks.min(-1), self.c(POS))
            if not (min_slack < thresh).any():
                break        # no edge can export, nor in a later round
            load = np.where(edge_valid, busy + np.where(
                self.eq_valid, self.eq_te, self.c(0)).sum(-1, dtype=self.ft),
                self.c(POS)).reshape(n, g)
            lead, best = _masked_arg(load, valid, is_max=False)
            is_lead = e == lead[:, None]
            runner_up = np.where(is_lead, self.c(POS), load).min(-1)
            dst_load = np.where(is_lead, runner_up[:, None],
                                best[:, None]).reshape(-1)
            exportable = (self.eq_valid & (slacks < thresh)
                          & (now + dst_load[:, None] + self.eq_te
                             <= self.eq_dl)).any(-1)
            over = ((min_slack < thresh) & exportable
                    & edge_valid).reshape(n, g)
            sidx, _ = _masked_arg(min_slack.reshape(n, g), over,
                                  is_max=False)
            src = np.maximum(sidx, 0)
            didx, _ = _masked_arg(load, valid & (e != src[:, None]),
                                  is_max=False)
            dst = np.maximum(didx, 0)
            s_at, d_at = base + src, base + dst
            cand = (self.eq_valid[s_at] & (slacks[s_at] < thresh)
                    & (now + load[np.arange(n), dst][:, None]
                       + self.eq_te[s_at] <= self.eq_dl[s_at]))
            vidx, _ = _masked_arg(slacks[s_at], cand, is_max=False)
            free = ~self.eq_valid[d_at]
            go = (over.any(-1) & (sidx >= 0) & (didx >= 0) & (vidx >= 0)
                  & free.any(-1))
            if not go.any():
                break        # nothing moved, nor will in a later round
            si, di = s_at[go], d_at[go]
            vi, slot = vidx[go], np.argmax(free[go], -1)
            self.eq_valid[si, vi] = False
            self.eq_valid[di, slot] = True
            for a in (self.eq_key, self.eq_te, self.eq_dl, self.eq_abs,
                      self.eq_model):
                a[di, slot] = a[si, vi]
            self.eq_seq[di, slot] = self.seq[di]
            self.seq[di] += 1
            self.n_peer_out[si] += 1
            self.n_peer_in[di] += 1
            moved = int(go.sum())
            self.counters["peer_out"] = self.counters.get("peer_out", 0) \
                + moved
            self.counters["peer_in"] = self.counters.get("peer_in", 0) \
                + moved

    # -- one tick --------------------------------------------------------
    def step(self, x: dict) -> dict:
        """Advance one tick; ``x`` holds the tick's signal row
        (``now`` a scalar; ``cloud_up`` a scalar or per edge; ``theta``,
        ``bw``, ``load_mult``,
        ``valid``, ``edge_up``, ``link_up`` per edge; ``arrive``,
        ``order`` per edge and model; ``exec_jit`` ``[E, M, 2]``).
        Returns the tick's fleet-summed counters and outcome deltas."""
        if not np.all(x["valid"]):
            raise ValueError("padded (valid=False) cells are not modelled")
        ft = self.ft
        now = ft(x["now"])
        bw = np.asarray(x["bw"], ft)
        clipped = np.maximum(bw, self.c(1e-3))
        seg = self.c(self.p.segment_kb * 8.0)
        bw_pen = seg / clipped - self.c(self.p.segment_kb * 8.0
                                        / self.p.nominal_bw_mbps)
        theta = np.asarray(x["theta"], ft)
        jit = np.asarray(x["exec_jit"], ft)
        edge_up = np.asarray(x["edge_up"], bool)
        before = (self.n_success.sum(), self.n_miss.sum(), self.n_drop.sum(),
                  self.n_stolen.sum())
        self.counters = {k: 0 for k in COUNTERS}
        cloud_up = np.broadcast_to(np.asarray(x["cloud_up"], bool), (self.E,))
        link_up = np.asarray(x["link_up"], bool)
        # only edges with a matured cloud-queue task have anything to resolve
        rows = np.nonzero((self.cq_valid & (self.cq_trig <= now)).any(-1)
                          & cloud_up & link_up)[0]
        if len(rows):
            sub = self._rows(rows)
            sub._resolve_cloud(now, theta[rows], bw_pen[rows],
                               cloud_up[rows], link_up[rows], jit[rows, :, 1])
            self._put_rows(rows, sub)
        self.cq_blocked = self.cq_blocked & self.cq_valid
        order = np.asarray(x["order"])
        arrive = np.asarray(x["arrive"], bool)
        load_mult = np.asarray(x["load_mult"], ft)
        for i in range(self.M):
            mdl = order[:, i].astype(np.int64)
            # an edge with no arrival in this slot is left as it is
            rows = np.nonzero(arrive[np.arange(self.E), mdl])[0]
            if not len(rows):
                continue
            sub = self._rows(rows)
            sub._route(now, mdl[rows], np.ones(len(rows), bool),
                       load_mult[rows], edge_up[rows])
            self._put_rows(rows, sub)
        self._execute(now, jit[:, :, 0], edge_up)
        if self.p.cooperation:
            self._peer_offload(now + self.c(self.p.dt),
                               np.asarray(x["valid"], bool) & edge_up)
        after = (self.n_success.sum(), self.n_miss.sum(), self.n_drop.sum(),
                 self.n_stolen.sum())
        rec = dict(self.counters)
        for k, a, b in zip(OUTCOMES, after, before):
            rec[k] = int(a - b)
        return rec

    # -- what the comparison reads ----------------------------------------
    def outcome(self) -> dict:
        """Integer end state per edge: outcome counters and occupancy."""
        return dict(
            n_success=self.n_success, n_miss=self.n_miss,
            n_drop=self.n_drop, n_stolen=self.n_stolen,
            n_edge_exec=self.n_edge_exec,
            n_peer_out=self.n_peer_out, n_peer_in=self.n_peer_in,
            eq_depth=self.eq_valid.sum(-1).astype(np.int32),
            cq_depth=self.cq_valid.sum(-1).astype(np.int32))
