"""Fleet-scale SPMD scheduler simulation (paper §8.6, TPU-native).

The paper weak-scales its platform to 84 drones / 28 edges by replicating
containers.  Here the *entire fleet* is one JAX program: per-edge scheduler
state is a PyTree of arrays with a leading ``fleet`` axis, each tick applies
the decision kernels of :mod:`repro.core.jax_sched` under ``vmap``, and the
fleet axis is sharded across devices with ``NamedSharding`` — the same
program scales from 1 edge on CPU to 10⁵ edges on a pod.

Modeling simplifications vs the event-driven oracle (documented per §Design):

* fixed time step ``dt`` (default 25 ms) instead of an event heap;
* deterministic execution fractions (edge ``edge_frac·t``, cloud
  ``cloud_frac·t̂ + θ(t) + bw-penalty``) — variability enters via the
  shaped θ trace and the dense cellular-bandwidth signal ``bw`` (the
  signed transfer penalty convention of
  :meth:`repro.sim.network.CloudLatencyModel.shaped_delta`);
* the cloud is a **finite pool**: each edge owns ``cloud_slots``
  busy-until slots (its share of the bounded FaaS concurrency, mirroring
  the oracle's per-edge ``cloud_concurrency``).  A matured task only
  dispatches when a slot is free; while the pool is saturated it stays
  parked on the trigger-time queue (still stealable) and the estimated
  queue-wait — the *depth-aware* k-th order statistic of the slot
  busy-until times, k being the task's cloud-queue position — is folded
  into the t̂ used by routing, migration, stealing triggers and GEMS
  feasibility.  With a large pool the wait is identically zero and the
  elastic model is recovered exactly (bit-identical to the old
  ``min(busy_until) − now`` estimate, which is the k=0 special case);
* tasks matured in the same tick dispatch in queue-slot order (the oracle
  pops in trigger order) — indistinguishable in the elastic limit, an
  approximation under saturation;
* estimator/offer events are batched per tick against the tick's
  pre-state (the oracle interleaves them in event order within one
  instant): DEMS-A observations apply as one masked window update
  (:func:`repro.core.jax_sched.adapt_feed_batch`), and a tick's cloud
  offers (migration victims + the arrival) are admitted in one
  vectorized pass that fills free queue slots in the exact order a
  sequential push loop would.

Supported policies: the oracle's full registry — the §8.2 baselines
(edge-only EDF/HPF, cloud-only CLD, EDF/SJF-E+C, the SOTA1/SOTA2
Kalmia-and-Dedas adaptations), DEM migration, DEMS work stealing with
trigger-time cloud queue and steal-only parking, DEMS-A sliding-window
cloud-latency adaptation (§5.4), GEMS window rescheduling and the
beyond-paper GEMS-B winnability budget.  Per-policy decision rules and
the oracle↔fleet semantic deltas are documented in ``docs/POLICIES.md``;
``tests/test_fleet_jax.py`` checks single-edge agreement with the
discrete-event engine for every policy.

Policy flags are **runtime values** (:class:`PolicyParams`): the compiled
tick program is policy-generic, so a whole scenario × policy × seed sweep
shares one executable.  Sweeps run as *one* compiled program through
:func:`run_fleet_batch` (same-shape replicas, :func:`stack_signals`) or —
across *heterogeneous* scenarios — through :func:`run_batch` on a
:func:`build_fleet_batch` batch, whose :func:`pad_signals` masks every
replica to the max (ticks, edges, models) shape with per-(tick, edge)
validity; padded cells are exact no-ops.  With a 2-D device mesh the
batch shards over a (replica, edge) grid.

The compiled tick scan is exposed step-wise through
:class:`FleetProgram` — ``init`` / ``step_chunk(state, signal_window)``
— the seam between *replaying a scenario* and *running a fleet*: every
replay entry point above is a thin :meth:`FleetProgram.run` loop over
``step_chunk`` (bitwise-identical to the pre-refactor single-scan
calls), and the online :class:`repro.serve.controller.FleetController`
feeds the very same ``step_chunk`` with telemetry-built windows.

Every entry point takes a ``trace=`` :class:`repro.obs.trace.TraceSpec`
— the flight recorder.  It taps the tick scan's carry and emits dense
per-tick decision counters and/or the adapted-t̂ stream as extra scan
outputs (:class:`FleetResult`); the taps are read-only and
valid-masked, so traced runs produce bit-identical scheduler results,
and a trace-off run compiles the very same program as before the
recorder existed.  Host-side aggregation (QoS/QoE time series, tail
percentiles, conservation ledger, Perfetto export) lives in
:mod:`repro.obs.metrics`.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import jax_sched as js
from repro.core import schedulers as _sched
from repro.core.task import ModelProfile
from repro.kernels import sched_ops
from repro.obs import trace as obs_trace
from repro.obs.trace import (TickCounters, TraceSpec, hist_counts,
                             resolve_spec, zero_counters)
from repro.sim import network

EDGE_CAP = 32
CLOUD_CAP = 64
SUBSTEPS = 6      # max edge executor actions (drops/starts) per tick
CLOUD_SLOTS = 16  # default per-edge FaaS share (engine's cloud_concurrency)


# Fleet-supported policy names: the oracle's full registry.  Flag sets
# derive from core.schedulers._POLICIES so the two simulators cannot
# drift apart.
_FLEET_POLICY_NAMES = tuple(_sched._POLICIES)
_FLEET_FLAGS = ("migration", "stealing", "gems", "adaptive", "use_cloud",
                "use_edge", "edge_feasibility_check", "edge_priority",
                "cloud_accepts_negative", "sota1", "sota2", "gems_budget")
_FLEET_POLICIES = {
    name: {k: v for k, v in _sched._POLICIES[name].items()
           if k in _FLEET_FLAGS}
    for name in _FLEET_POLICY_NAMES
}


class PolicyParams(NamedTuple):
    """Policy flags as traced scalars (leading replica axis in batches).

    Making the flags runtime values keeps the compiled tick program
    policy-generic: one executable serves every policy (and, stacked, a
    whole registry × policy × seed sweep), at the price of computing each
    feature's masked no-op when its flag is off.
    """

    migration: jax.Array        # bool[]
    stealing: jax.Array         # bool[]
    gems: jax.Array             # bool[]
    use_cloud: jax.Array        # bool[]
    use_edge: jax.Array         # bool[]  False → CLD (cloud-only routing)
    feas_check: jax.Array       # bool[]  False → EDF/HPF unconditional insert
    edge_prio: jax.Array        # i32[]   jax_sched.PRIO_{EDF,HPF,SJF}
    cloud_neg_ok: jax.Array     # bool[]  SJF-E+C sends γ^C≤0 tasks anyway
    sota1: jax.Array            # bool[]  Kalmia/D3 urgency routing (§8.2)
    sota2: jax.Array            # bool[]  Dedas ACT routing (§8.2)
    gems_budget: jax.Array      # bool[]  GEMS-B winnability gate
    urgent_deadline: jax.Array  # f32[]   SOTA1 urgency threshold [ms]
    adaptive: jax.Array         # bool[]
    cooperation: jax.Array      # bool[]
    cloud_margin: jax.Array     # f32[]
    adapt_eps: jax.Array        # f32[]
    adapt_cooling_ms: jax.Array  # f32[]
    coop_slack_ms: jax.Array    # f32[]
    coop_transfer_cap: jax.Array  # i32[] (≤ the program's static rounds)
    cloud_give_up_ms: jax.Array  # f32[] parked-dispatch timeout (+inf = off)


@dataclasses.dataclass(frozen=True)
class FleetPolicy:
    """Policy flags (subset of core.schedulers.Policy).

    Lowered to runtime :class:`PolicyParams` by :meth:`params`; only
    ``adapt_window`` (a buffer *shape*) and ``coop_max_transfers`` (a
    loop bound) stay trace-time static.
    """

    migration: bool = False
    stealing: bool = False
    gems: bool = False
    use_cloud: bool = True
    use_edge: bool = True
    edge_feasibility_check: bool = True
    edge_priority: str = "edf"            # "edf" | "hpf" | "sjf"
    cloud_accepts_negative: bool = False
    sota1: bool = False
    sota2: bool = False
    gems_budget: bool = False
    urgent_deadline: float = 700.0        # SOTA1 urgency threshold [ms]
    cloud_margin: float = 50.0
    # DEMS-A sliding-window cloud-latency adaptation (§5.4): estimator
    # hyper-parameters mirror core.schedulers.AdaptiveEstimator.
    adaptive: bool = False
    adapt_window: int = 10
    adapt_eps: float = 10.0
    adapt_cooling_ms: float = 10_000.0
    # cross-edge cooperation (beyond-paper; fleet-scope work stealing):
    # after each tick, edges whose minimum queue slack drops below
    # ``coop_slack_ms`` export their worst-slack feasible tasks to the
    # least-loaded peer, at most ``coop_max_transfers`` moves per tick.
    cooperation: bool = False
    coop_slack_ms: float = 0.0
    coop_max_transfers: int = 2
    # cloud-dispatch timeout (chaos hardening): a parked cloud task that
    # has waited more than this past its trigger maturity — through an
    # outage, a partition, or pool saturation — is dropped instead of
    # retried forever.  The fleet re-checks every tick, the oracle at
    # every dispatch/recovery event: timeout with bounded retries, the
    # shared convention.  +inf (the default) disables the timeout and is
    # a bitwise no-op on every existing result.
    cloud_give_up_ms: float = float("inf")

    @classmethod
    def from_name(cls, name: str) -> "FleetPolicy":
        coop = name.endswith("-COOP")
        base_name = name[: -len("-COOP")] if coop else name
        if base_name not in _FLEET_POLICIES:
            supported = sorted(_FLEET_POLICIES) + sorted(
                n + "-COOP" for n in _FLEET_POLICIES)
            raise ValueError(f"unknown fleet policy {name!r}; choose from "
                             f"{supported}")
        base = cls(**_FLEET_POLICIES[base_name])
        return dataclasses.replace(base, cooperation=True) if coop else base

    def params(self) -> PolicyParams:
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        prio = {"edf": js.PRIO_EDF, "hpf": js.PRIO_HPF,
                "sjf": js.PRIO_SJF}[self.edge_priority]
        return PolicyParams(
            migration=jnp.asarray(self.migration),
            stealing=jnp.asarray(self.stealing),
            gems=jnp.asarray(self.gems),
            use_cloud=jnp.asarray(self.use_cloud),
            use_edge=jnp.asarray(self.use_edge),
            feas_check=jnp.asarray(self.edge_feasibility_check),
            edge_prio=jnp.asarray(prio, jnp.int32),
            cloud_neg_ok=jnp.asarray(self.cloud_accepts_negative),
            sota1=jnp.asarray(self.sota1),
            sota2=jnp.asarray(self.sota2),
            gems_budget=jnp.asarray(self.gems_budget),
            urgent_deadline=f32(self.urgent_deadline),
            adaptive=jnp.asarray(self.adaptive),
            cooperation=jnp.asarray(self.cooperation),
            cloud_margin=f32(self.cloud_margin),
            adapt_eps=f32(self.adapt_eps),
            adapt_cooling_ms=f32(self.adapt_cooling_ms),
            coop_slack_ms=f32(self.coop_slack_ms),
            coop_transfer_cap=jnp.asarray(self.coop_max_transfers,
                                          jnp.int32),
            cloud_give_up_ms=f32(self.cloud_give_up_ms))


class Profiles(NamedTuple):
    """Array-of-struct model table (M models)."""

    t_edge: jax.Array
    t_cloud: jax.Array
    deadline: jax.Array
    gamma_e: jax.Array
    gamma_c: jax.Array
    cost_e: jax.Array
    cost_c: jax.Array
    steal_rank: jax.Array
    qoe_alpha: jax.Array
    qoe_beta: jax.Array
    qoe_window: jax.Array

    @classmethod
    def build(cls, models: list[ModelProfile],
              pad_to: Optional[int] = None) -> "Profiles":
        """Build the table; ``pad_to`` appends inert models for padded
        cross-scenario batching.  Pad values are chosen so no reduction
        over the model axis can see them: huge latencies keep
        ``min(t_edge)`` (the stealing gate) and window expiry untouched,
        zero utilities keep every masked sum exact."""
        f = jnp.asarray
        prof = cls(
            t_edge=f([m.t_edge for m in models], jnp.float32),
            t_cloud=f([m.t_cloud for m in models], jnp.float32),
            deadline=f([m.deadline for m in models], jnp.float32),
            gamma_e=f([m.gamma_edge for m in models], jnp.float32),
            gamma_c=f([m.gamma_cloud for m in models], jnp.float32),
            cost_e=f([m.cost_edge for m in models], jnp.float32),
            cost_c=f([m.cost_cloud for m in models], jnp.float32),
            steal_rank=f([m.steal_rank() for m in models], jnp.float32),
            qoe_alpha=f([m.qoe_alpha for m in models], jnp.float32),
            qoe_beta=f([m.qoe_beta for m in models], jnp.float32),
            qoe_window=f([m.qoe_window for m in models], jnp.float32),
        )
        if pad_to is None or pad_to <= len(models):
            return prof
        pad_val = dict(t_edge=js.POS, t_cloud=js.POS, deadline=js.POS,
                       qoe_window=js.POS)
        width = pad_to - len(models)
        return cls(**{
            name: jnp.concatenate([getattr(prof, name), jnp.full(
                width, pad_val.get(name, 0.0), jnp.float32)])
            for name in cls._fields})


class EdgeState(NamedTuple):
    """Per-edge scheduler state (leading fleet axis added by vmap)."""

    eq: js.EdgeQueue
    cq: js.CloudQueue
    cq_model: jax.Array        # i32[Qc] model ids of cloud-queued tasks
    busy_rem: jax.Array        # f32[] remaining edge execution time
    # finite FaaS pool: busy-until time per cloud slot (this edge's share
    # of the bounded Lambda concurrency; slot free iff busy_until <= now).
    # In padded batches the array is oversized and slots ≥ n_slots are
    # parked at +inf — never free, invisible to the k-th order statistic.
    cloud_busy_until: jax.Array  # f32[S]
    n_slots: jax.Array         # i32[] this edge's real pool depth
    # cloud-queue entries that have waited for a saturated pool at least
    # once: when their slot finally frees they re-run the oracle's
    # dispatch-time JIT check (never set in the elastic limit)
    cq_blocked: jax.Array      # bool[Qc]
    seq: jax.Array             # i32[] insertion counter
    # stats
    n_success: jax.Array       # i32[M]
    n_miss: jax.Array          # i32[M]
    n_drop: jax.Array          # i32[M]
    n_stolen: jax.Array        # i32[M]
    n_edge_exec: jax.Array     # i32[M] tasks executed on the edge
    qos_utility: jax.Array     # f32[]
    # GEMS window state
    lam: jax.Array             # i32[M]
    lam_hat: jax.Array         # i32[M]
    # per-window arrival forecast (GEMS-B): events seen in the *previous*
    # window, the base of the winnability check's remaining-arrival
    # estimate (oracle _WindowState.prev_lam)
    prev_lam: jax.Array        # i32[M]
    win_end: jax.Array         # f32[M]
    qoe_utility: jax.Array     # f32[]
    windows_met: jax.Array     # i32[M]
    # cross-edge cooperation stats
    n_peer_out: jax.Array      # i32[] tasks exported to a peer edge
    n_peer_in: jax.Array       # i32[] tasks imported from a peer edge
    # DEMS-A estimator state (§5.4): per-model sliding-window t̂
    adapt: js.AdaptState


class FleetResult(NamedTuple):
    """A fleet run with flight-recorder telemetry (``trace=TraceSpec``).

    ``t_hat`` carries ``adapt.current`` out of the tick scan — the
    scheduler's per-tick adapted cloud-latency estimate, enabling
    Fig. 12-style adaptation-dynamics plots.  Its shape is ``[T, E, M]``
    from :func:`run_fleet` and ``[R, T, E, M]`` from both batch entry
    points (:func:`run_fleet_batch` and :func:`run_batch`), where T is
    the tick count, E the (padded) edge count, M the (padded) model
    count and R the replica count.  ``counters`` carries the per-tick
    decision stream (:class:`repro.obs.trace.TickCounters`, leaves
    ``[T, E, …]`` / ``[R, T, E, …]``).  Streams not requested by the
    :class:`~repro.obs.trace.TraceSpec` are ``None``.
    """

    final: EdgeState
    t_hat: Optional[jax.Array] = None        # f32[(R,) T, E, M]
    counters: Optional[TickCounters] = None  # [(R,) T, E, …] leaves


def _tr_add(tr: Optional[TickCounters], **deltas) -> Optional[TickCounters]:
    """Accumulate trace contributions; statically a no-op when the
    flight recorder is off (``tr is None``), so the untraced program is
    byte-identical to the pre-recorder one."""
    if tr is None:
        return None
    return tr._replace(**{k: getattr(tr, k) + v for k, v in deltas.items()})


def init_state(prof: Profiles, adapt_window: int = 10,
               cloud_slots: int = CLOUD_SLOTS,
               total_slots: Optional[int] = None) -> EdgeState:
    """Fresh per-edge state.  ``total_slots`` oversizes the busy-until
    array for padded batches; slots beyond ``cloud_slots`` start (and
    stay) at +inf so they are never free."""
    m = prof.t_edge.shape[0]
    total = cloud_slots if total_slots is None else total_slots
    zi = jnp.zeros(m, jnp.int32)
    return EdgeState(
        eq=js.empty_edge_queue(EDGE_CAP), cq=js.empty_cloud_queue(CLOUD_CAP),
        cq_model=jnp.zeros(CLOUD_CAP, jnp.int32),
        busy_rem=jnp.zeros(()),
        # strong f32 (not a weak Python-float fill): the stepped state
        # comes back strongly typed, and a weak→strong aval flip would
        # retrace the program on the second step_chunk window
        cloud_busy_until=jnp.where(jnp.arange(total) < cloud_slots,
                                   0.0, js.POS).astype(jnp.float32),
        n_slots=jnp.asarray(cloud_slots, jnp.int32),
        cq_blocked=jnp.zeros(CLOUD_CAP, bool),
        seq=jnp.zeros((), jnp.int32),
        n_success=zi, n_miss=zi, n_drop=zi, n_stolen=zi, n_edge_exec=zi,
        qos_utility=jnp.zeros(()),
        lam=zi, lam_hat=zi, prev_lam=zi, win_end=prof.qoe_window,
        qoe_utility=jnp.zeros(()), windows_met=zi,
        n_peer_out=jnp.zeros((), jnp.int32),
        n_peer_in=jnp.zeros((), jnp.int32),
        adapt=js.adapt_init(prof.t_cloud, adapt_window))


def _pool_wait(st: EdgeState, now) -> jax.Array:
    """Depth-aware queue-wait estimate for the next dispatch-bound task.

    The task joining the cloud queue sits behind ``pending`` entries that
    will each grab a slot, so it waits for the k-th slot to free — the
    k-th order statistic of the busy-until times (ROADMAP item), not the
    time until *one* slot frees.  With an empty queue this reduces to the
    old ``min(busy_until) − now``; in the elastic limit (ample pool) it
    is identically zero, bit-for-bit."""
    pending = (st.cq.valid & ~st.cq.steal_only).sum()
    k = jnp.clip(pending, 0, st.n_slots - 1)
    return jnp.maximum(jnp.sort(st.cloud_busy_until)[k] - now, 0.0)


def _free_slot_gate(busy_until: jax.Array, now,
                    want: jax.Array) -> jax.Array:
    """Admit the first ``n_free`` wanting tasks, in slot order.

    ``want`` marks queue entries that would each occupy one cloud slot;
    the gate is True for those that find a free slot this tick (tasks
    popped-and-dropped without dispatching never consume a slot, so they
    are gated by the same dispatch count — as in the oracle's pop loop).
    """
    wi = want.astype(jnp.int32)
    taken_before = jnp.cumsum(wi) - wi          # exclusive dispatch count
    return taken_before < (busy_until <= now).sum()


def _occupy_slots(busy_until: jax.Array, now, dispatch: jax.Array,
                  end_time: jax.Array) -> jax.Array:
    """Assign each dispatched task a distinct free slot, vectorized.

    Dispatched task k (in queue order) fills the k-th free slot with its
    completion time; ``dispatch`` must already be gated by
    :func:`_free_slot_gate` so ranks never exceed the free count.
    """
    s = busy_until.shape[0]
    di = dispatch.astype(jnp.int32)
    drank = jnp.cumsum(di) - di
    end_by_rank = jnp.zeros(s).at[
        jnp.where(dispatch, drank, s)].set(end_time, mode="drop")
    free = busy_until <= now
    fi = free.astype(jnp.int32)
    frank = jnp.cumsum(fi) - fi
    fill = free & (frank < dispatch.sum())
    return jnp.where(fill, end_by_rank[frank], busy_until)


def _t_cloud_cur(st: EdgeState, prof: Profiles, pp: PolicyParams,
                 now) -> jax.Array:
    """Scheduler's current cloud-latency estimate t̂ per model (§5.4),
    plus the depth-aware finite-pool queue-wait estimate (zero while the
    pool has headroom), so routing, migration, stealing triggers and GEMS
    feasibility all see the congested cloud."""
    base = jnp.where(pp.adaptive, st.adapt.current, prof.t_cloud)
    return base + _pool_wait(st, now)


class FleetSignals(NamedTuple):
    """Dense per-tick scenario signals driving the fleet simulator.

    Produced either by :func:`default_signals` (the paper's steady
    3-drones-per-edge workload) or by
    :func:`repro.scenarios.compile.compile_fleet` (mobility, handover,
    bursts, churn, outages, heterogeneous edges).  ``valid`` marks the
    live (tick, edge) cells: all-True for a plain run, the real-region
    mask after :func:`pad_signals`; the tick function reverts every
    invalid cell to its pre-tick state, making padding exact.
    """

    times: jax.Array       # f32[T]    tick start times [ms]
    theta: jax.Array       # f32[T,E]  per-edge added WAN latency θ(t)
    bw: jax.Array          # f32[T,E]  per-edge cellular bandwidth [Mbps]
    arrive: jax.Array      # bool[T,E,M] model m arrives at edge e this tick
    order: jax.Array       # i32[T,E,M] randomized insertion order (§3.3)
    load_mult: jax.Array   # f32[T,E]  edge execution-time multiplier
    cloud_up: jax.Array    # bool[T]   cloud FaaS availability
    valid: jax.Array       # bool[T,E] live cells (False ⇒ padded no-op)
    # sampled execution-duration multipliers, axis -1 = (edge, cloud);
    # exactly 1.0 in deterministic mode, so the default lane is a
    # bitwise no-op on every act computation it scales
    exec_jit: jax.Array    # f32[T,E,M,2]
    # chaos-engine availability lanes (repro.faults): all-True outside a
    # fault schedule, so fault-free signals compile to the same program
    # results as before the lanes existed
    edge_up: jax.Array     # bool[T,E] False ⇒ edge crashed (queue flushed)
    link_up: jax.Array     # bool[T,E] False ⇒ edge↔cloud link partitioned


# ---------------------------------------------------------------------------
# per-tick logic for one edge
# ---------------------------------------------------------------------------

def _resolve_cloud(st: EdgeState, tr: Optional[TickCounters],
                   tspec: TraceSpec, prof: Profiles, pp: PolicyParams, now,
                   theta, bw_pen, cloud_frac, cloud_up, link_up, jit_c):
    """Dispatch matured cloud tasks into the finite FaaS pool.

    During a cloud outage (``cloud_up`` False) matured tasks stay parked
    on the trigger-time queue; the dispatch-time deadline check settles
    their fate once the cloud returns — mirroring the oracle's behavior.
    Likewise, while the slot pool is saturated, matured tasks stay parked
    (still stealable, like the oracle's ``cloud_pending``) and retry once
    a slot frees; a dispatched task occupies its slot for the whole
    actual duration ``cloud_frac·t̂ + θ(t) + bw-penalty``.

    With ``pp.adaptive`` (DEMS-A, §5.4) dispatch adds the oracle's JIT
    check against the *adapted* estimate t̂: tasks it predicts to miss are
    skipped (dropped, feeding the cooling timer) instead of dispatched —
    without consuming a slot; dispatched tasks fire ``on_sent`` and
    ``observe`` their actual duration, applied as one batched masked
    window update (:func:`repro.core.jax_sched.adapt_feed_batch`).
    """
    # a partitioned edge↔cloud link parks dispatch exactly like a cloud
    # outage seen from this edge; the per-edge link_up lane composes with
    # the fleet-wide cloud_up mask
    mature = st.cq.valid & (st.cq.trigger <= now) & cloud_up & link_up
    # cloud-dispatch timeout (bounded retries): a parked task that has
    # waited more than cloud_give_up_ms past its trigger maturity —
    # through an outage, a partition, or pool saturation — gives up and
    # drops.  +inf (the default) never fires.
    timed_out = st.cq.valid & ~st.cq.steal_only & \
        (now - st.cq.trigger > pp.cloud_give_up_ms)
    run = mature & ~st.cq.steal_only & ~timed_out
    fits_a = now + st.adapt.current[st.cq_model] <= st.cq.deadline
    # the oracle JIT-checks every pop against the static estimate; in
    # the fleet model tasks normally mature within one tick of their
    # feasibility-checked trigger, so the check is redundant — except
    # for tasks that sat out a saturated pool, which re-run it here
    # (never taken in the elastic limit).  Outage-parked tasks keep
    # the documented modeling simplification of settling via the
    # dispatch-time deadline check instead (the oracle JIT-drops them
    # at recovery without consuming a slot); under a small pool the
    # difference is bounded to one pool-depth of doomed dispatches,
    # since everything behind them fails the slot gate, turns
    # cq_blocked, and does re-run this check.
    fits_s = ~st.cq_blocked | (now + prof.t_cloud[st.cq_model]
                               <= st.cq.deadline)
    fits = jnp.where(pp.adaptive, fits_a, fits_s)
    avail = _free_slot_gate(st.cloud_busy_until, now, run & fits)
    dispatch = run & fits & avail
    skipped = run & ~fits & avail     # popped + JIT-dropped, slot stays free
    # the sampled multiplier scales the compute body only — θ(t) and the
    # bandwidth penalty stay additive, like the oracle's shaped_delta
    act = cloud_frac * prof.t_cloud[st.cq_model] * jit_c[st.cq_model] \
        + theta + bw_pen
    success = dispatch & (now + act <= st.cq.deadline)
    util = jnp.where(success, prof.gamma_c[st.cq_model],
                     jnp.where(dispatch, -prof.cost_c[st.cq_model],
                               0.0)).sum()
    add = functools.partial(jax.ops.segment_sum,
                            num_segments=prof.t_edge.shape[0])
    n_success = st.n_success + add(success.astype(jnp.int32), st.cq_model)
    n_miss = st.n_miss + add((dispatch & ~success).astype(jnp.int32),
                             st.cq_model)
    dropped = mature & st.cq.steal_only      # not stolen in time (§5.3)
    n_drop = st.n_drop + add((dropped | skipped | timed_out)
                             .astype(jnp.int32), st.cq_model)
    # flight recorder: read-only taps (drops by cause, pool pressure,
    # tail evidence from the settled tasks' slack/latency)
    tr = _tr_add(
        tr, cloud_dispatch=dispatch.sum(), pool_blocked=(run & ~avail).sum(),
        drop_infeasible=skipped.sum(), drop_unstolen=dropped.sum(),
        drop_timeout=timed_out.sum(),
        slack_hist=hist_counts(st.cq.deadline - (now + act), success, tspec),
        latency_hist=hist_counts(
            (now + act) - (st.cq.deadline - prof.deadline[st.cq_model]),
            success, tspec))
    settled = dispatch | skipped | dropped | timed_out  # blocked stay parked
    new_valid = st.cq.valid & ~settled
    st = st._replace(cq=st.cq._replace(valid=new_valid),
                     cloud_busy_until=_occupy_slots(
                         st.cloud_busy_until, now, dispatch, now + act),
                     cq_blocked=(st.cq_blocked | (run & ~avail)) & new_valid,
                     n_success=n_success, n_miss=n_miss, n_drop=n_drop,
                     qos_utility=st.qos_utility + util)
    sent = dispatch & pp.adaptive
    st = st._replace(adapt=js.adapt_feed_batch(
        st.adapt, st.cq_model, sent, sent, act, skipped & pp.adaptive,
        now, prof.t_cloud, pp.adapt_eps, pp.adapt_cooling_ms,
        max_obs=st.cloud_busy_until.shape[0]))
    return _gems_bulk(st, prof, success & pp.gems,
                      (dispatch | skipped | dropped | timed_out) & pp.gems,
                      st.cq_model), tr


def _gems_bulk(st: EdgeState, prof: Profiles, success_mask, done_mask,
               model_ids) -> EdgeState:
    """Window counters for a batch of task completions/drops."""
    m = prof.t_edge.shape[0]
    add = functools.partial(jax.ops.segment_sum, num_segments=m)
    lam = st.lam + add(done_mask.astype(jnp.int32), model_ids)
    lam_hat = st.lam_hat + add(success_mask.astype(jnp.int32), model_ids)
    return st._replace(lam=lam, lam_hat=lam_hat)


def _gems_act(st: EdgeState, tr: Optional[TickCounters], tspec: TraceSpec,
              prof: Profiles, pp: PolicyParams, now, theta, bw_pen,
              cloud_frac, link_up, jit_c):
    """Alg. 1: reschedule lagging models, close expired windows.

    Rescheduled tasks go through the same finite pool as the dispatch
    path: the feasibility gate sees the queue-wait-folded t̂, moves are
    capped by the free slots this tick (the rest stay on the edge queue
    and may move next tick if still lagging), and each move occupies a
    slot for the actual-duration model ``cloud_frac·t̂ + θ + bw-penalty``.

    Plain GEMS keeps the legacy modeling simplification of resolving the
    move's *outcome* at the deterministic estimate t̂ (no shaping) — the
    elastic-limit behavior this refactor preserves bit-for-bit; only
    GEMS-A resolves at the actual-duration model and feeds completions to
    the estimator (mirroring the oracle, where rescheduled tasks go
    through the instrumented cloud dispatch path).

    GEMS-B (``pp.gems_budget``, beyond-paper) adds the winnability gate:
    once a window is mathematically lost (per the ``prev_lam`` arrival
    forecast) the Alg-1 flood stops, and only tasks already *doomed* on
    the edge (projected completion past their scheduling deadline) still
    move — a pure QoS rescue, since no QoE is recoverable this window.
    """
    m = prof.t_edge.shape[0]
    rate = st.lam_hat / jnp.maximum(st.lam, 1)
    lagging = (st.lam > 0) & (rate < prof.qoe_alpha)
    lost = pp.gems_budget & ~js.gems_winnable(
        st.lam, st.lam_hat, st.prev_lam, prof.qoe_alpha, now, st.win_end,
        prof.qoe_window)
    proj = js.projected_completions(st.eq, now,
                                    jnp.maximum(st.busy_rem, 0.0))
    doomed = proj > st.eq.deadline

    # move pending edge tasks of lagging models to the cloud (trigger=now,
    # resolved immediately into the free slots of the finite pool);
    # feasibility and success use the absolute deadline, as in the
    # oracle's rescan/dispatch path.
    t_hat = _t_cloud_cur(st, prof, pp, now)
    feas = now + t_hat[st.eq.model] <= st.eq.abs_dl
    # a partitioned link halts GEMS pool migration across it (the lane
    # is all-True outside a fault schedule, so this gate is free)
    cand = (st.eq.valid & lagging[st.eq.model]
            & (prof.gamma_c[st.eq.model] > 0) & feas) & pp.gems & link_up
    want = cand & (~lost[st.eq.model] | doomed)
    move = want & _free_slot_gate(st.cloud_busy_until, now, want)
    # slots are *held* for the actual duration either way; only the
    # outcome model differs between GEMS (estimate) and GEMS-A (actual)
    hold = cloud_frac * prof.t_cloud[st.eq.model] * jit_c[st.eq.model] \
        + theta + bw_pen
    act = jnp.where(pp.adaptive, hold, prof.t_cloud[st.eq.model])
    success = move & (now + act <= st.eq.abs_dl)
    tr = _tr_add(
        tr, gems_moved=move.sum(),
        gems_withheld=(cand & lost[st.eq.model] & ~doomed).sum(),
        slack_hist=hist_counts(st.eq.abs_dl - (now + act), success, tspec),
        latency_hist=hist_counts(
            (now + act) - (st.eq.abs_dl - prof.deadline[st.eq.model]),
            success, tspec))
    add = functools.partial(jax.ops.segment_sum, num_segments=m)
    util = jnp.where(success, prof.gamma_c[st.eq.model],
                     jnp.where(move, -prof.cost_c[st.eq.model], 0.0)).sum()
    fed = move & pp.adaptive
    st = st._replace(adapt=js.adapt_feed_batch(
        st.adapt, st.eq.model, fed, fed, act,
        jnp.zeros_like(fed), now, prof.t_cloud, pp.adapt_eps,
        pp.adapt_cooling_ms, max_obs=st.cloud_busy_until.shape[0]))
    st = st._replace(
        eq=js.edge_remove(st.eq, move),
        cloud_busy_until=_occupy_slots(st.cloud_busy_until, now, move,
                                       now + hold),
        n_success=st.n_success + add(success.astype(jnp.int32), st.eq.model),
        n_miss=st.n_miss + add((move & ~success).astype(jnp.int32),
                               st.eq.model),
        qos_utility=st.qos_utility + util)
    st = _gems_bulk(st, prof, success, move, st.eq.model)

    # tumbling-window close (Eqn 2)
    expired = (now > st.win_end) & pp.gems
    met = expired & (st.lam > 0) & (st.lam_hat / jnp.maximum(st.lam, 1)
                                    >= prof.qoe_alpha)
    qoe = jnp.where(met, prof.qoe_beta, 0.0).sum()
    return st._replace(
        lam=jnp.where(expired, 0, st.lam),
        lam_hat=jnp.where(expired, 0, st.lam_hat),
        # closing window's event count becomes the next window's arrival
        # forecast (GEMS-B winnability base)
        prev_lam=jnp.where(expired, st.lam, st.prev_lam),
        win_end=jnp.where(expired, st.win_end + prof.qoe_window, st.win_end),
        qoe_utility=st.qoe_utility + qoe,
        windows_met=st.windows_met + met.astype(jnp.int32)), tr


def _offer_cloud_many(st: EdgeState, prof: Profiles, pp: PolicyParams, now,
                      models, deadlines, t_edges, enable,
                      t_cur=None) -> tuple[EdgeState, jax.Array]:
    """Vectorized cloud admission (Policy.offer_cloud) for a task batch.

    ``enable`` marks offered candidates in slot order; accepted ones fill
    the cloud queue's free slots in ascending order — exactly the slots a
    sequential ``cloud_push`` loop would pick.  Every policy check reads
    the tick's pre-offer state (batched-per-tick: an earlier offer in the
    same batch does not shift a later one's queue-depth estimate — the
    module-header simplification).  ``t_edges`` are the tasks' *effective*
    edge latencies (speed factor folded in), kept on the cloud queue for
    steal decisions.

    Feasibility and trigger times use the DEMS-A-adapted t̂ when the
    policy is adaptive — plus the finite-pool queue-wait estimate, so a
    congested cloud pulls stealing triggers earlier and fails the
    feasibility gate sooner; a policy-level rejection then counts as a
    *skip* for the estimator's cooling logic (oracle ``_offer_cloud``).
    Returns ``(state, pushed, accepted)`` — ``accepted & ~pushed`` lost
    the race for a free queue slot (a capacity drop, not a policy one);
    ``t_cur`` lets the caller reuse an already-computed
    :func:`_t_cloud_cur` vector for the same state.
    """
    if t_cur is None:
        t_cur = _t_cloud_cur(st, prof, pp, now)
    t_hat = t_cur[models]
    feasible = now + t_hat <= deadlines
    # SJF-E+C (cloud_neg_ok) sends γ^C≤0 tasks to the cloud anyway; every
    # other policy rejects (or, stealing, parks) them
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


def _route_arrival(st: EdgeState, tr: Optional[TickCounters],
                   prof: Profiles, pp: PolicyParams, now,
                   model, arrive, load_mult, edge_up=True):
    """Task-scheduler routing for one arriving task (§5.1–5.2, §8.2).

    ``load_mult`` is the edge's speed factor: the effective edge latency
    ``load_mult·t_edge`` is stored on the queues, so feasibility, JIT
    checks, stealing and execution all see the heterogeneous speed —
    matching the oracle compiler, which folds it into the model table.

    Every routing rule of the oracle registry is a runtime branch of the
    same program: the queue position comes from the policy's priority key
    (EDF deadline / HPF utility rate / SJF execution time), ``use_edge``
    off sends everything cloud-ward (CLD), ``feas_check`` off inserts
    unconditionally (edge-only EDF/HPF; the executor's JIT check culls
    late heads), SOTA1 retries infeasible non-urgent tasks with a 10 %
    *scheduling-only* deadline buffer, and SOTA2 admits a
    single-violation insert only when it lowers the queue's mean
    completion time (Dedas ACT rule).

    Migration victims and the redirected arrival go to the cloud through
    *one* vectorized :func:`_offer_cloud_many` call (victims in queue-slot
    order, then the arrival — the same admission order as the old
    sequential offer loop); cloud offers always use the *absolute*
    deadline.
    """
    abs_dl = now + prof.deadline[model]
    te = prof.t_edge[model] * load_mult
    key0 = js.edge_priority_key(pp.edge_prio, abs_dl, te,
                                prof.gamma_e[model])
    feas0 = js.insert_feasible(st.eq, now, st.busy_rem, key0, te, abs_dl)
    victims = js.victim_mask(st.eq, now, st.busy_rem, key0, te)

    # SOTA1 (Kalmia+D3): an infeasible non-urgent task retries with a
    # 10 % deadline buffer; success is still judged at abs_dl, so bought
    # slack can turn into an edge miss — the adaptation's known cost.
    sched1 = abs_dl + 0.1 * prof.deadline[model]
    feas1 = js.insert_feasible(st.eq, now, st.busy_rem, sched1, te, sched1)
    take_ext = (pp.sota1 & ~feas0 & feas1
                & (prof.deadline[model] > pp.urgent_deadline))

    # SOTA2 (Dedas): violations caused by the insert — none: insert;
    # more than one: cloud; exactly one: keep the schedule whose mean
    # completion time is lower (inserting nearly always raises it).
    nviol = victims.sum() + (~feas0).astype(jnp.int32)
    act_ok = js.act_improves(st.eq, now, st.busy_rem, key0, te)
    sota2_ok = (nviol == 0) | ((nviol == 1) & feas0 & act_ok)

    t_cur = _t_cloud_cur(st, prof, pp, now)
    migrate_ok = js.migration_decision(
        st.eq, victims, now, model, abs_dl, prof.gamma_e,
        prof.gamma_c, t_cur)
    plain_ok = feas0 & jnp.where(pp.migration,
                                 ~victims.any() | migrate_ok, True)
    edge_ok = jnp.where(pp.sota1, feas0 | take_ext,
                        jnp.where(pp.sota2, sota2_ok,
                                  jnp.where(pp.feas_check, plain_ok,
                                            True)))
    # a crashed edge admits nothing: arrivals re-route cloudward (and
    # drop there for cloudless policies), matching the oracle's crashed
    # _route convention
    insert_edge = arrive & pp.use_edge & edge_ok & edge_up
    vic = victims & insert_edge & pp.migration
    to_cloud = arrive & ~insert_edge
    key = jnp.where(take_ext, sched1, key0)
    sched_dl = jnp.where(take_ext, sched1, abs_dl)

    models = jnp.concatenate([st.eq.model, jnp.asarray(model)[None]])
    dls = jnp.concatenate([st.eq.abs_dl, jnp.asarray(abs_dl)[None]])
    tes = jnp.concatenate([st.eq.t_edge, jnp.asarray(te)[None]])
    offer = jnp.concatenate([vic, jnp.asarray(to_cloud)[None]])
    st, pushed, accepted = _offer_cloud_many(st, prof, pp, now, models, dls,
                                             tes, offer, t_cur=t_cur)
    add = functools.partial(jax.ops.segment_sum,
                            num_segments=prof.t_edge.shape[0])
    eq = js.edge_remove(st.eq, vic)
    eq, ok = js.edge_push(eq, key, st.seq, te, sched_dl, model,
                          enable=insert_edge, abs_dl=abs_dl)
    # a full edge queue loses the task (edge-only policies cannot shed to
    # the cloud): account it as a drop so tasks stay conserved
    lost = (insert_edge & ~ok).astype(jnp.int32)
    tr = _tr_add(
        tr, arrivals=arrive.astype(jnp.int32),
        admit_edge=(insert_edge & ok).astype(jnp.int32),
        admit_cloud=pushed.sum(), migrated=vic.sum(),
        drop_infeasible=(offer & ~accepted).sum(),
        drop_qfull=lost + (offer & accepted & ~pushed).sum())
    return st._replace(
        eq=eq, seq=st.seq + arrive.astype(jnp.int32),
        n_drop=st.n_drop.at[model].add(lost)
        + add((offer & ~pushed).astype(jnp.int32), models)), tr


def _edge_execute(st: EdgeState, tr: Optional[TickCounters],
                  tspec: TraceSpec, prof: Profiles, pp: PolicyParams, now,
                  dt, edge_frac, min_edge_t, jit_e, edge_up=True):
    """Edge executor: JIT drops, stealing, starting the next task.

    Queue entries carry the *effective* edge latency (speed factor folded
    in at insert time), so every check and the executed duration reflect
    heterogeneous edge speeds consistently.

    A crashed edge (``edge_up`` False) flushes its queue as drops and
    suspends stealing/starts; the task in flight at crash time still
    completes (``busy_rem`` keeps draining — the model is a scheduler
    crash, not a power cut), and the restart resumes with an empty queue.
    The oracle's crash handler mirrors both choices.
    """
    m_ids = jnp.arange(prof.t_edge.shape[0], dtype=jnp.int32)

    flush = st.eq.valid & ~edge_up
    st = st._replace(
        eq=js.edge_remove(st.eq, flush),
        n_drop=st.n_drop + jax.ops.segment_sum(
            flush.astype(jnp.int32), st.eq.model,
            num_segments=prof.t_edge.shape[0]))
    st = _gems_bulk(st, prof, jnp.zeros_like(flush),
                    flush & pp.gems, st.eq.model)
    tr = _tr_add(tr, drop_crash=flush.sum())

    def body(_, carry):
        s, tr = carry
        idle = s.busy_rem <= 0.0

        # JIT check on the head
        eq_after, head_idx, found = js.edge_pop_head(s.eq)
        head_model = s.eq.model[head_idx]
        head_dl = s.eq.deadline[head_idx]
        head_te = s.eq.t_edge[head_idx]
        head_infeasible = found & (now + head_te > head_dl)
        do_drop = idle & head_infeasible
        s = s._replace(
            eq=jax.tree.map(lambda a, b: jnp.where(do_drop, a, b),
                            eq_after, s.eq),
            n_drop=s.n_drop.at[head_model].add(do_drop.astype(jnp.int32)))
        s = _gems_bulk(s, prof, jnp.zeros_like(m_ids, bool),
                       (m_ids == head_model) & do_drop & pp.gems, m_ids)

        idle = idle & ~head_infeasible
        # stealing (§5.3)
        sidx = js.steal_select(s.cq, s.eq, now,
                               jnp.maximum(s.busy_rem, 0.0), min_edge_t)
        can_steal = idle & (sidx >= 0) & pp.stealing & edge_up
        smodel = s.cq_model[jnp.maximum(sidx, 0)]
        sdl = s.cq.deadline[jnp.maximum(sidx, 0)]
        ste = s.cq.t_edge[jnp.maximum(sidx, 0)]
        s = s._replace(cq=s.cq._replace(
            valid=jnp.where(can_steal,
                            s.cq.valid.at[jnp.maximum(sidx, 0)].set(
                                False), s.cq.valid)),
            n_stolen=s.n_stolen.at[smodel].add(
                can_steal.astype(jnp.int32)))

        # start next task: stolen task first, else the queue head
        eq_after, head_idx, found = js.edge_pop_head(s.eq)
        start_head = idle & ~can_steal & found
        run_model = jnp.where(can_steal, smodel, s.eq.model[head_idx])
        # success is judged at the *absolute* deadline (cloud-queue
        # deadlines already are; SOTA1's scheduling extension must not
        # turn a late finish into a success)
        run_dl = jnp.where(can_steal, sdl, s.eq.abs_dl[head_idx])
        run_te = jnp.where(can_steal, ste, s.eq.t_edge[head_idx])
        start = can_steal | start_head
        act = edge_frac * run_te * jit_e[run_model]
        success = start & (now + act <= run_dl)
        util = jnp.where(success, prof.gamma_e[run_model],
                         jnp.where(start, -prof.cost_e[run_model], 0.0))
        tr = _tr_add(
            tr, drop_infeasible=do_drop.astype(jnp.int32),
            edge_exec=start.astype(jnp.int32),
            slack_hist=hist_counts(run_dl - (now + act), success, tspec),
            latency_hist=hist_counts(
                (now + act) - (run_dl - prof.deadline[run_model]),
                success, tspec))
        s = s._replace(
            eq=jax.tree.map(lambda a, b: jnp.where(start_head, a, b),
                            eq_after, s.eq),
            # carry sub-tick execution debt so tick quantization does not
            # waste edge throughput (finish mid-tick → next task starts
            # from the leftover, like the continuous-time oracle)
            busy_rem=jnp.where(start, s.busy_rem + act, s.busy_rem),
            n_success=s.n_success.at[run_model].add(
                success.astype(jnp.int32)),
            n_edge_exec=s.n_edge_exec.at[run_model].add(
                start.astype(jnp.int32)),
            n_miss=s.n_miss.at[run_model].add(
                (start & ~success).astype(jnp.int32)),
            qos_utility=s.qos_utility + util)
        run_onehot = (m_ids == run_model) & start & pp.gems
        return _gems_bulk(s, prof, run_onehot & success, run_onehot,
                          m_ids), tr

    st, tr = jax.lax.fori_loop(0, SUBSTEPS, body, (st, tr))
    # at most one tick of banked debt; idle edges do not accumulate credit
    return st._replace(busy_rem=jnp.maximum(st.busy_rem - dt, -dt)), tr


def make_step(dt: float, edge_frac: float, cloud_frac: float,
              tspec: TraceSpec = TraceSpec()):
    """Build the policy-generic single-edge tick function (vmapped over
    the fleet); ``prof``/``pp`` are runtime arguments, so one compiled
    step serves every model table and policy in a batch.

    With ``tspec.counters`` the step also returns a
    :class:`~repro.obs.trace.TickCounters` of this tick's decisions —
    every tap is read-only on the scheduler state, so the traced run's
    summaries are bit-identical to the untraced run's; without it the
    second return value is ``None`` and the compiled program is the same
    one as before the flight recorder existed.
    """

    def step(prof: Profiles, pp: PolicyParams, st: EdgeState, inputs):
        # arrive: bool[M]; order: i32[M]; theta/bw/load_mult/valid per-edge
        (now, theta, bw, arrive, order, load_mult, cloud_up, valid,
         exec_jit, edge_up, link_up) = inputs
        # signed cellular transfer penalty (network.py convention); exactly
        # 0.0 at the nominal benchmark bandwidth
        bw_pen = network.bandwidth_penalty_ms(bw)
        # per-model sampled duration multipliers for this (tick, edge)
        jit_e, jit_c = exec_jit[:, 0], exec_jit[:, 1]
        min_edge_t = prof.t_edge.min()     # padded models sit at +inf
        st0 = st
        tr = zero_counters(prof.t_edge.shape[0], tspec) \
            if tspec.counters else None
        st, tr = _resolve_cloud(st, tr, tspec, prof, pp, now, theta, bw_pen,
                                cloud_frac, cloud_up, link_up, jit_c)

        # §3.3: tasks of a segment are inserted in randomized order; the
        # loop is load-bearing — each insertion's feasibility depends on
        # the same tick's earlier insertions — but its per-arrival cloud
        # offers are batched inside _route_arrival
        def route_one(i, carry):
            s, t = carry
            mdl = order[i]
            return _route_arrival(s, t, prof, pp, now, mdl, arrive[mdl],
                                  load_mult, edge_up)
        st, tr = jax.lax.fori_loop(0, prof.t_edge.shape[0], route_one,
                                   (st, tr))
        st, tr = _edge_execute(st, tr, tspec, prof, pp, now, dt, edge_frac,
                               min_edge_t, jit_e, edge_up)
        st, tr = _gems_act(st, tr, tspec, prof, pp, now, theta, bw_pen,
                           cloud_frac, link_up, jit_c)
        # padded (tick, edge) cells are exact no-ops
        st = jax.tree.map(lambda a, b: jnp.where(valid, a, b), st, st0)
        if tr is not None:
            # event counters zero out on padded cells; outcome counters
            # are post-revert state deltas (so they sum to the final
            # summary stats exactly), and gauges read the (possibly
            # reverted) end-of-tick state so the conservation ledger
            # stays exact through a padded tail
            tr = tr._replace(**{
                f: jnp.where(valid, getattr(tr, f),
                             jnp.zeros_like(getattr(tr, f)))
                for f in obs_trace.EVENT_FIELDS})
            tr = tr._replace(
                hit=st.n_success - st0.n_success,
                miss=st.n_miss - st0.n_miss,
                drop=st.n_drop - st0.n_drop,
                stolen=st.n_stolen - st0.n_stolen,
                qos=st.qos_utility - st0.qos_utility,
                qoe=st.qoe_utility - st0.qoe_utility,
                eq_depth=st.eq.valid.sum().astype(jnp.int32),
                cq_depth=st.cq.valid.sum().astype(jnp.int32),
                slots_busy=((st.cloud_busy_until > now + dt)
                            & (jnp.arange(st.cloud_busy_until.shape[0])
                               < st.n_slots)).sum().astype(jnp.int32),
                valid=valid)
        return st, tr

    return step


# ---------------------------------------------------------------------------
# cross-edge peer offload (fleet-level exchange between ticks)
# ---------------------------------------------------------------------------

def peer_offload(fs: EdgeState, now, slack_ms, max_transfers: int, *,
                 enable=True, transfer_cap=None,
                 edge_valid=None) -> EdgeState:
    """Move doomed tasks from overloaded edges to the least-loaded peer.

    Operates on the *stacked* fleet state (leading edge axis).  Each of
    the ``max_transfers`` rounds picks the worst-min-slack edge *among
    those with an actually exportable task* (so an unexportable straggler
    cannot starve other overloaded edges), selects its worst-slack task
    that is still feasible behind the least-loaded other edge's queue,
    and re-homes it — the paper's §5.3 work-stealing idea lifted from
    edge↔cloud to edge↔edge.  Queue ``t_edge`` entries carry the source
    edge's speed factor; destination feasibility reuses them, which is
    conservative when the destination is faster.  Under a sharded fleet
    axis the gathers/scatters lower to cross-device collectives.

    ``max_transfers`` is the static round bound; ``enable`` (the runtime
    cooperation flag) and ``transfer_cap`` (the runtime per-tick cap, ≤
    the bound) mask rounds off per replica, and ``edge_valid`` excludes
    padded edges from both export and import.
    """
    n_edges = fs.busy_rem.shape[0]
    if n_edges < 2 or max_transfers == 0:
        return fs
    ev = jnp.ones(n_edges, bool) if edge_valid is None else edge_valid
    cap = jnp.asarray(max_transfers if transfer_cap is None else
                      transfer_cap, jnp.int32)

    def one_transfer(k, fs: EdgeState) -> EdgeState:
        busy = jnp.maximum(fs.busy_rem, 0.0)
        slacks = jax.vmap(js.queue_slacks, in_axes=(0, None, 0))(
            fs.eq, now, busy)                              # [E, Q]
        min_slack = jnp.where(ev, slacks.min(-1), js.POS)  # [E]
        load = jnp.where(ev, jax.vmap(js.queue_load)(fs.eq, fs.busy_rem),
                         js.POS)                           # [E]

        # each edge's best available destination load (least-loaded other
        # edge): the global minimum, or the runner-up for that edge itself
        lead, best = sched_ops.masked_argmin(load, ev)
        runner_up = jnp.where(jnp.arange(n_edges) == lead, js.POS,
                              load).min()
        dst_load = jnp.where(jnp.arange(n_edges) == lead, runner_up,
                             best)                         # [E]
        exportable = (fs.eq.valid & (slacks < slack_ms)
                      & (now + dst_load[:, None] + fs.eq.t_edge
                         <= fs.eq.deadline)).any(-1)       # [E]
        over = (min_slack < slack_ms) & exportable & ev
        sidx, _ = sched_ops.masked_argmin(min_slack, over)
        src = jnp.maximum(sidx, 0)
        didx, _ = sched_ops.masked_argmin(
            load, ev & (jnp.arange(n_edges) != src))
        dst = jnp.maximum(didx, 0)

        src_eq = jax.tree.map(lambda a: a[src], fs.eq)
        vidx = js.export_select(src_eq, now, busy[src], load[dst], slack_ms)
        ok = (over.any() & (sidx >= 0) & (didx >= 0) & (vidx >= 0)
              & enable & (k < cap))
        vi = jnp.maximum(vidx, 0)

        free = ~fs.eq.valid[dst]
        ok = ok & free.any()
        slot = jnp.argmax(free)
        eq = fs.eq
        moved = js.EdgeQueue(
            valid=eq.valid.at[src, vi].set(False).at[dst, slot].set(True),
            key=eq.key.at[dst, slot].set(src_eq.key[vi]),
            seq=eq.seq.at[dst, slot].set(fs.seq[dst]),
            t_edge=eq.t_edge.at[dst, slot].set(src_eq.t_edge[vi]),
            deadline=eq.deadline.at[dst, slot].set(src_eq.deadline[vi]),
            abs_dl=eq.abs_dl.at[dst, slot].set(src_eq.abs_dl[vi]),
            model=eq.model.at[dst, slot].set(src_eq.model[vi]))
        new_eq = jax.tree.map(lambda a, b: jnp.where(ok, a, b), moved, eq)
        oki = ok.astype(jnp.int32)
        return fs._replace(
            eq=new_eq,
            seq=fs.seq.at[dst].add(oki),
            n_peer_out=fs.n_peer_out.at[src].add(oki),
            n_peer_in=fs.n_peer_in.at[dst].add(oki))

    return jax.lax.fori_loop(0, max_transfers, one_transfer, fs)


def default_signals(n_models: int, *, n_edges: int, drones_per_edge: int = 3,
                    duration_ms: float = 300_000.0, dt: float = 25.0,
                    theta_fn=None, bw_fn=None, seed: int = 0) -> FleetSignals:
    """The paper's steady workload as dense tick signals (§8.1/§8.6).

    ``theta_fn`` / ``bw_fn`` shape the WAN latency and cellular bandwidth
    (defaults: no added latency, nominal bandwidth → zero transfer
    penalty).
    """
    m = n_models
    n_ticks = int(duration_ms / dt)
    rng = np.random.default_rng(seed)

    # one segment per drone per second → per-tick arrival counts; we spread
    # each drone's per-segment task burst across model slots determin.
    times = np.arange(n_ticks, dtype=np.float32) * dt
    arrive = np.zeros((n_ticks, n_edges, m), dtype=bool)
    for e in range(n_edges):
        for d in range(drones_per_edge):
            phase = rng.uniform(0, 1000.0)
            seg_t = np.arange(phase, duration_ms, 1000.0)
            ticks = np.minimum((seg_t / dt).astype(int), n_ticks - 1)
            arrive[ticks, e, :] = True
    theta_t = network.sample_trace(theta_fn, times) if theta_fn \
        else np.zeros(n_ticks, np.float32)
    theta = np.broadcast_to(theta_t[:, None], (n_ticks, n_edges))
    bw_t = network.sample_trace(bw_fn, times) if bw_fn \
        else np.full(n_ticks, network.NOMINAL_BW_MBPS, np.float32)
    bw = np.broadcast_to(bw_t[:, None], (n_ticks, n_edges))
    order = rng.permuted(np.tile(np.arange(m), (n_ticks, n_edges, 1)),
                         axis=2).astype(np.int32)
    return FleetSignals(
        times=jnp.asarray(times), theta=jnp.asarray(theta),
        bw=jnp.asarray(bw), arrive=jnp.asarray(arrive),
        order=jnp.asarray(order),
        load_mult=jnp.ones((n_ticks, n_edges), jnp.float32),
        cloud_up=jnp.ones(n_ticks, bool),
        valid=jnp.ones((n_ticks, n_edges), bool),
        exec_jit=jnp.ones((n_ticks, n_edges, m, 2), jnp.float32),
        edge_up=jnp.ones((n_ticks, n_edges), bool),
        link_up=jnp.ones((n_ticks, n_edges), bool))


def _resolve_policy(policy) -> FleetPolicy:
    return policy if isinstance(policy, FleetPolicy) \
        else FleetPolicy.from_name(policy)


# ---------------------------------------------------------------------------
# mesh sharding
# ---------------------------------------------------------------------------

def _on_mesh(mesh: Optional[jax.sharding.Mesh]):
    """The context a sharded program traces and runs under: with
    ``jax.set_mesh`` the selection sites see a multi-device mesh and
    take the partitionable reference over the Mosaic kernel
    (:func:`repro.kernels.sched_ops.masked_argext`)."""
    return jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()


def _put(a: jax.Array, mesh: jax.sharding.Mesh, names: tuple) -> jax.Array:
    """Place ``a`` with the given per-axis mesh-axis names (None = rep.);
    axes whose size does not divide the mesh axis stay replicated."""
    spec = []
    for i in range(a.ndim):
        n = names[i] if i < len(names) else None
        if n is not None and a.shape[i] % mesh.shape[n] != 0:
            n = None
        spec.append(n)
    return jax.device_put(a, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec)))


def _shard_leading(tree, mesh: jax.sharding.Mesh, axes: int = 1):
    """Shard every leaf's first ``axes`` dims over the mesh's first axes.

    ``axes=2`` is the (replica, edge) grid of a padded batch: replicas
    fan out over the first mesh axis, each replica's fleet over the
    second — the 2-D NamedSharding of the ROADMAP item.
    """
    names = mesh.axis_names[:axes]
    return jax.tree.map(lambda a: _put(a, mesh, names), tree)


# tick-signal leaves keep the replica axis leading; the edge axis sits at
# a field-dependent position (None = no edge axis)
_SIGNAL_EDGE_AXIS = dict(times=None, theta=2, bw=2, arrive=2, order=2,
                         load_mult=2, cloud_up=None, valid=2, exec_jit=2,
                         edge_up=2, link_up=2)


def _shard_signals(sig: FleetSignals, mesh: jax.sharding.Mesh
                   ) -> FleetSignals:
    """Shard batched signals ``[R, T, …]``: replicas over the first mesh
    axis and (on a 2-D mesh) the edge axis over the second."""
    r = mesh.axis_names[0]
    e = mesh.axis_names[1] if len(mesh.axis_names) > 1 else None
    out = {}
    for f in FleetSignals._fields:
        a = getattr(sig, f)
        names = [None] * a.ndim
        names[0] = r
        ax = _SIGNAL_EDGE_AXIS[f]
        if e is not None and ax is not None:
            names[ax] = e
        out[f] = _put(a, mesh, tuple(names))
    return FleetSignals(**out)


# ---------------------------------------------------------------------------
# compiled fleet programs (cached: policy and profiles are runtime args,
# so a program is reused across every policy/scenario of the same shape)
# ---------------------------------------------------------------------------

# every live compiled program, for retrace accounting
# (repro.obs.prof.fleet_compile_stats): a program jit-traces once per
# input *shape* — policies are runtime data, so running more policies
# through it must add no traces (tests/conftest.py ``compile_guard``)
_PROGRAM_REGISTRY: list = []

# The program cache is bounded: the shape-bucketed sweep planner
# (:func:`plan_buckets`) deliberately keys one executable per bucket
# layout, and a long-lived process sweeping many bucket shapes must not
# accumulate jit wrappers (and their trace caches) without bound.  LRU
# order: the programs a sweep is actively cycling through stay resident;
# evicted programs also leave ``_PROGRAM_REGISTRY`` so retrace
# accounting tracks live executables only.
FLEET_PROGRAM_CACHE_CAPACITY = 32
_PROGRAM_CACHE: collections.OrderedDict = collections.OrderedDict()
_PROGRAM_EVICTIONS = 0


def _fleet_program(dt: float, edge_frac: float, cloud_frac: float,
                   coop_rounds: int, tspec: TraceSpec, batched: bool,
                   hetero: bool, donate: bool = False):
    """Jitted ``run(prof, pp, state, xs)``.

    ``batched`` adds a leading replica axis on the signals (and, when
    ``hetero``, on profiles/params/state too).  ``coop_rounds`` is the
    static peer-offload round bound (0 compiles cooperation out
    entirely); per-replica runtime caps mask rounds within it.
    ``tspec`` selects the flight-recorder streams tapped out of the scan;
    it is part of this cache's key, so the trace-off program is the very
    executable the untraced sweeps always compiled.  ``donate`` hands the
    ``state`` argument's buffers to XLA (``donate_argnums``): the carry
    is updated in place instead of round-tripping fresh allocations each
    chunk — callers must not reuse a donated input afterwards
    (:meth:`FleetProgram.run` copies the caller's initial state once).
    """
    global _PROGRAM_EVICTIONS
    key = (dt, edge_frac, cloud_frac, coop_rounds, tspec, batched, hetero,
           donate)
    prog = _PROGRAM_CACHE.get(key)
    if prog is not None:
        _PROGRAM_CACHE.move_to_end(key)
        return prog
    step = make_step(dt, edge_frac, cloud_frac, tspec)

    def run(prof, pp, state, xs):
        vstep = jax.vmap(step, in_axes=(
            None, None, 0, (None, 0, 0, 0, 0, 0, None, 0, 0, 0, 0)))

        def scan_body(state, xs_t):
            now = xs_t[0]
            valid = xs_t[7]
            edge_up = xs_t[9]
            state, tick = vstep(prof, pp, state, xs_t)
            if coop_rounds:
                pre_out, pre_in = state.n_peer_out, state.n_peer_in
                # crashed edges neither export nor import peer work
                state = peer_offload(
                    state, now + dt, pp.coop_slack_ms, coop_rounds,
                    enable=pp.cooperation,
                    transfer_cap=pp.coop_transfer_cap,
                    edge_valid=valid & edge_up)
                if tick is not None:
                    # the exchange runs on the stacked fleet state between
                    # ticks; fold its per-edge deltas into the tick row
                    tick = tick._replace(
                        peer_out=tick.peer_out + state.n_peer_out - pre_out,
                        peer_in=tick.peer_in + state.n_peer_in - pre_in)
            ys = (state.adapt.current if tspec.t_hat else None, tick)
            return state, ys

        final, (t_hat, counters) = jax.lax.scan(scan_body, state, xs)
        if tspec.enabled:
            return FleetResult(final, t_hat, counters)
        return final

    if batched:
        ax = 0 if hetero else None
        run = jax.vmap(run, in_axes=(ax, ax, ax, 0))
    prog = jax.jit(run, donate_argnums=(2,)) if donate else jax.jit(run)
    _PROGRAM_CACHE[key] = prog
    _PROGRAM_REGISTRY.append(prog)
    while len(_PROGRAM_CACHE) > FLEET_PROGRAM_CACHE_CAPACITY:
        _, evicted = _PROGRAM_CACHE.popitem(last=False)
        _PROGRAM_EVICTIONS += 1
        try:
            _PROGRAM_REGISTRY.remove(evicted)
        except ValueError:  # already dropped by reset_fleet_programs
            pass
    return prog


def _program_cache_clear() -> None:
    _PROGRAM_CACHE.clear()


# keep the lru_cache-era management surface: callers
# (benchmarks/bench_fleet.py, repro.obs.prof.reset_fleet_programs) clear
# the cache through the function object
_fleet_program.cache_clear = _program_cache_clear


def slice_signals(sig: FleetSignals, lo: int, hi: int, *,
                  tick_axis: int = 0) -> FleetSignals:
    """Ticks ``[lo, hi)`` of a signal tree as a window (``tick_axis=1``
    for batched ``[R, T, …]`` signals).  Every :class:`FleetSignals`
    field carries its tick axis in the same position, so a plain tree
    slice is a well-formed window."""
    idx = (slice(None),) * tick_axis + (slice(lo, hi),)
    return jax.tree.map(lambda a: a[idx], sig)


@dataclasses.dataclass(frozen=True)
class FleetProgram:
    """The compiled tick program as a *step-wise* control-plane API.

    ``init`` builds the stacked per-edge scheduler state;
    :meth:`step_chunk` advances it over one dt-aligned
    :class:`FleetSignals` window and returns the new state plus the
    window's flight-recorder streams.  Because each tick reads only the
    carried state and its own signal row, scanning a horizon in one call
    or chunk-by-chunk is the *same computation* — the replay entry
    points (:func:`run_fleet`, :func:`run_fleet_batch`,
    :func:`run_batch`) are thin :meth:`run` loops over ``step_chunk``
    with bitwise-identical results, and the online
    :class:`repro.serve.controller.FleetController` calls ``step_chunk``
    directly on telemetry-built windows.

    The jitted executable is shared through the :func:`_fleet_program`
    cache: two programs with equal static fields reuse one compile, and
    a chunk compiles once per distinct window length.

    ``donate=True`` compiles the executable with its state argument's
    buffers donated to XLA: the scan carry updates in place instead of
    allocating a fresh state every chunk — the steady-state win of the
    metropolis-scale path.  A donated :meth:`step_chunk` *consumes* the
    state you pass it (the input buffers are invalidated); :meth:`run`
    copies the caller's initial state once so replay callers can keep
    reusing their batches.
    """

    dt: float = 25.0
    edge_frac: float = 0.62
    cloud_frac: float = 0.80
    coop_rounds: int = 0
    trace: TraceSpec = TraceSpec()
    batched: bool = False
    hetero: bool = False
    donate: bool = False

    @classmethod
    def for_policy(cls, policy, *, trace: TraceSpec = TraceSpec(),
                   dt: float = 25.0, edge_frac: float = 0.62,
                   cloud_frac: float = 0.80, batched: bool = False,
                   hetero: bool = False, donate: bool = False
                   ) -> "FleetProgram":
        """A program whose static peer-offload bound matches ``policy``."""
        pol = _resolve_policy(policy)
        return cls(dt=dt, edge_frac=edge_frac, cloud_frac=cloud_frac,
                   coop_rounds=pol.coop_max_transfers if pol.cooperation
                   else 0, trace=trace, batched=batched, hetero=hetero,
                   donate=donate)

    def init(self, prof: Profiles, policy, n_edges: int,
             cloud_slots: int = CLOUD_SLOTS,
             total_slots: Optional[int] = None) -> EdgeState:
        """Fresh stacked fleet state (leading edge axis), exactly the
        state every replay entry point starts from."""
        pol = _resolve_policy(policy)
        return jax.vmap(
            lambda _: init_state(prof, pol.adapt_window, cloud_slots,
                                 total_slots=total_slots))(
            jnp.arange(n_edges))

    @property
    def _jitted(self):
        return _fleet_program(self.dt, self.edge_frac, self.cloud_frac,
                              self.coop_rounds, self.trace, self.batched,
                              self.hetero, self.donate)

    def lower(self, prof, pp, state, signals) -> jax.stages.Lowered:
        """The chunk executable lowered for these arguments (arrays, or
        ``ShapeDtypeStruct`` s placed on described devices); ``.compile()``
        it for ``memory_analysis()`` or to check a compile for a chip."""
        return self._jitted.lower(prof, pp, state, tuple(signals))

    def step_chunk(self, prof: Profiles, pp: PolicyParams, state: EdgeState,
                   signals: FleetSignals):
        """Advance ``state`` over one signal window.

        Returns ``(state, result)`` — ``result`` is the window's
        :class:`FleetResult` (its trace streams cover only this window's
        ticks) when the program's :class:`~repro.obs.trace.TraceSpec` is
        enabled, else ``None``.  The call is bounded-latency: one jitted
        scan of ``window_ticks`` steps, no host round-trips inside.
        """
        out = self._jitted(prof, pp, state, tuple(signals))
        if self.trace.enabled:
            return out.final, out
        return out, None

    def run(self, prof: Profiles, pp: PolicyParams, state: EdgeState,
            signals: FleetSignals, chunk_ticks: Optional[int] = None):
        """Replay: loop :meth:`step_chunk` over the whole horizon.

        ``chunk_ticks=None`` runs the horizon as one chunk — the same
        single compiled call (and executable) the pre-refactor entry
        points made.  A finite ``chunk_ticks`` replays window-by-window,
        concatenating trace streams along the tick axis; results are
        bitwise identical either way.

        With ``donate`` on, the loop is *double-buffered*: the next
        window is sliced while the current chunk is still in flight
        (async dispatch overlaps host slicing with device compute) and
        the donated carry never round-trips a fresh allocation.  The
        caller's ``state`` buffers survive — the loop consumes a private
        copy.
        """
        tick_axis = 1 if self.batched else 0
        n_ticks = signals.times.shape[tick_axis]
        if self.donate:
            # the executable consumes its state input; replay callers
            # (e.g. a FleetBatch swept under several planners) keep
            # their initial state, so donate a copy instead
            state = jax.tree.map(jnp.copy, state)
        if chunk_ticks is None or chunk_ticks >= n_ticks:
            state, res = self.step_chunk(prof, pp, state, signals)
            return res if self.trace.enabled else state
        bounds = [(lo, min(lo + chunk_ticks, n_ticks))
                  for lo in range(0, n_ticks, chunk_ticks)]
        chunks = []
        win = slice_signals(signals, *bounds[0], tick_axis=tick_axis)
        for i in range(len(bounds)):
            nxt = slice_signals(signals, *bounds[i + 1],
                                tick_axis=tick_axis) \
                if i + 1 < len(bounds) else None
            state, res = self.step_chunk(prof, pp, state, win)
            win = nxt
            chunks.append(res)
            if self.donate and (i & 7) == 7:
                # bound in-flight work: sync on the *newest* carry only
                # — older states are already donated away and their
                # buffers are dead
                jax.block_until_ready(state)
        if not self.trace.enabled:
            return state

        def cat(parts):
            if parts[0] is None:
                return None
            return jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=tick_axis), *parts)

        return FleetResult(state, cat([c.t_hat for c in chunks]),
                           cat([c.counters for c in chunks]))


def run_fleet(models: list[ModelProfile], policy, signals: FleetSignals, *,
              dt: float = 25.0, edge_frac: float = 0.62,
              cloud_frac: float = 0.80, cloud_slots: int = CLOUD_SLOTS,
              mesh: Optional[jax.sharding.Mesh] = None,
              record_trace: bool = False,
              trace: Optional[TraceSpec] = None,
              chunk_ticks: Optional[int] = None,
              donate: bool = False):
    """Run the fleet simulator over arbitrary scenario signals.

    ``policy`` is a :class:`FleetPolicy` or a name (``"DEMS"``,
    ``"GEMS-A-COOP"``, …).  ``cloud_slots`` is each edge's share of the
    bounded FaaS concurrency (the oracle's ``cloud_concurrency``); make it
    large to recover the elastic-cloud limit.  With ``mesh`` given, fleet
    state is sharded over its first axis (pjit-style data parallelism over
    edges); the peer offload exchange then runs as cross-device
    collectives.

    ``trace`` turns on the flight recorder: a
    :class:`~repro.obs.trace.TraceSpec` selecting the per-tick streams,
    returned as a :class:`FleetResult` (``t_hat`` shaped ``[T, E, M]``
    here; tracing never changes the scheduler's results — the final
    state is bit-identical to the untraced run).  ``record_trace=True``
    is the deprecated alias for ``TraceSpec(t_hat=True)``.  The default
    returns just the final :class:`EdgeState`.

    This is a thin :meth:`FleetProgram.run` loop; ``chunk_ticks``
    replays the horizon in windows of that many ticks (bitwise-identical
    to the default whole-horizon chunk — the streaming controller's
    execution path).  ``donate=True`` compiles the program with its
    state buffers donated (in-place carry updates, double-buffered
    windows) — same results bitwise, see :class:`FleetProgram`.
    """
    tspec = resolve_spec(trace, record_trace)
    pol = _resolve_policy(policy)
    prof = Profiles.build(models)
    n_edges = signals.arrive.shape[1]
    prog = FleetProgram.for_policy(pol, trace=tspec, dt=dt,
                                   edge_frac=edge_frac,
                                   cloud_frac=cloud_frac, donate=donate)
    state = prog.init(prof, pol, n_edges, cloud_slots)
    if mesh is not None:
        state = _shard_leading(state, mesh)
    with _on_mesh(mesh):
        return prog.run(prof, pol.params(), state, signals, chunk_ticks)


def stack_signals(signals: list[FleetSignals]) -> FleetSignals:
    """Stack per-run signals over a new leading replica axis.

    All runs must share (n_ticks, n_edges, n_models) — i.e. seeds or event
    variants of one scenario shape, the unit :func:`run_fleet_batch`
    compiles once and sweeps in a single program.  Heterogeneous shapes
    raise a :class:`ValueError` naming the offending field; use
    :func:`pad_signals` for a cross-scenario batch.
    """
    for f in FleetSignals._fields:
        shapes = [tuple(getattr(s, f).shape) for s in signals]
        if any(sh != shapes[0] for sh in shapes):
            raise ValueError(
                f"stack_signals: replica signals disagree on field {f!r} "
                f"(shapes {shapes}); stack only same-shape replicas "
                f"(seeds / event variants of one scenario) or use "
                f"pad_signals for a heterogeneous cross-scenario batch")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *signals)


def pad_signals(signals: list[FleetSignals],
                dt: float = 25.0) -> FleetSignals:
    """Mask heterogeneous per-run signals to the max shape and stack.

    Every replica is padded to the batch's max (ticks, edges, models):
    padded ticks/edges carry ``valid=False`` (the tick function reverts
    them to exact no-ops), padded models never arrive and their ids are
    appended to the insertion ``order`` so it stays a permutation.  The
    result feeds :func:`build_fleet_batch` / :func:`run_batch`, which run
    the whole cross-scenario sweep as one compiled program.
    """
    sigs = [jax.tree.map(np.asarray, s) for s in signals]
    tmax = max(s.arrive.shape[0] for s in sigs)
    emax = max(s.arrive.shape[1] for s in sigs)
    mmax = max(s.arrive.shape[2] for s in sigs)
    padded = []
    for s in sigs:
        t, e, m = s.arrive.shape
        pt, pe = tmax - t, emax - e
        step = float(s.times[1] - s.times[0]) if t > 1 else dt
        times = np.concatenate(
            [s.times, s.times[-1] + step * np.arange(1, pt + 1,
                                                     dtype=np.float32)])
        order = np.broadcast_to(np.arange(mmax, dtype=np.int32),
                                (tmax, emax, mmax)).copy()
        order[:t, :e, :m] = s.order
        valid = np.zeros((tmax, emax), dtype=bool)
        valid[:t, :e] = s.valid
        padded.append(FleetSignals(
            times=times.astype(np.float32),
            theta=np.pad(s.theta, ((0, pt), (0, pe))),
            bw=np.pad(s.bw, ((0, pt), (0, pe)),
                      constant_values=network.NOMINAL_BW_MBPS),
            arrive=np.pad(s.arrive, ((0, pt), (0, pe),
                                     (0, mmax - m))),
            order=order,
            load_mult=np.pad(s.load_mult, ((0, pt), (0, pe)),
                             constant_values=1.0),
            cloud_up=np.pad(s.cloud_up, (0, pt), constant_values=True),
            valid=valid,
            # padded cells keep the deterministic ×1.0 multiplier
            exec_jit=np.pad(s.exec_jit,
                            ((0, pt), (0, pe), (0, mmax - m), (0, 0)),
                            constant_values=1.0),
            # padded cells are healthy (valid=False already no-ops them)
            edge_up=np.pad(s.edge_up, ((0, pt), (0, pe)),
                           constant_values=True),
            link_up=np.pad(s.link_up, ((0, pt), (0, pe)),
                           constant_values=True)))
    return jax.tree.map(lambda *xs: jnp.stack([np.asarray(x)
                                               for x in xs]), *padded)


def run_fleet_batch(models: list[ModelProfile], policy,
                    signals: FleetSignals, *, dt: float = 25.0,
                    edge_frac: float = 0.62, cloud_frac: float = 0.80,
                    cloud_slots: int = CLOUD_SLOTS,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    record_trace: bool = False,
                    trace: Optional[TraceSpec] = None,
                    donate: bool = False):
    """One-jit sweep: ``signals`` carry a leading replica axis ``[R, …]``
    (from :func:`stack_signals`), and the whole sweep — every replica's
    full mission scan — runs as a single ``vmap``-over-replicas compiled
    program instead of R sequential jits.

    Returns the stacked final :class:`EdgeState` with leading ``[R, E]``
    axes; slicing replica ``r`` reproduces ``run_fleet`` on that run's
    signals exactly.  With ``mesh`` given, replicas are sharded over its
    first axis; a 2-D mesh additionally shards the edge axis over its
    second (the (replica, edge) grid).  ``trace`` (or the deprecated
    ``record_trace`` alias for ``TraceSpec(t_hat=True)``) returns a
    :class:`FleetResult` instead, with replica-leading trace streams
    (``t_hat`` shaped ``[R, T, E, M]``).  For *heterogeneous* replicas
    (different scenarios / policies / pool depths) see
    :func:`build_fleet_batch` / :func:`run_batch`.
    """
    tspec = resolve_spec(trace, record_trace)
    pol = _resolve_policy(policy)
    prof = Profiles.build(models)
    n_edges = signals.arrive.shape[2]
    prog = FleetProgram.for_policy(pol, trace=tspec, dt=dt,
                                   edge_frac=edge_frac,
                                   cloud_frac=cloud_frac, batched=True,
                                   donate=donate)
    state = prog.init(prof, pol, n_edges, cloud_slots)
    if mesh is not None:
        # state is replica-shared (vmap in_axes None): leave it replicated
        # on a 1-D replica mesh; a 2-D mesh shards its edge axis over the
        # second mesh axis
        if len(mesh.axis_names) > 1:
            state = jax.tree.map(
                lambda a: _put(a, mesh, (mesh.axis_names[1],)), state)
        signals = _shard_signals(signals, mesh)
    with _on_mesh(mesh):
        return prog.run(prof, pol.params(), state, signals)


class FleetBatch(NamedTuple):
    """A heterogeneous sweep compiled to one program's inputs.

    ``profiles``/``params``/``state`` carry a leading replica axis
    matching ``signals``; ``coop_rounds`` is the static peer-offload
    bound (max across the batch's policies).
    """

    profiles: Profiles      # [R, Mp, …]
    params: PolicyParams    # [R]
    state: EdgeState        # [R, E, …]
    signals: FleetSignals   # [R, T, …]
    coop_rounds: int


def build_fleet_batch(runs, *, dt: float = 25.0) -> FleetBatch:
    """Assemble heterogeneous runs into one padded, stackable batch.

    ``runs`` is a list of ``(models, policy, signals, cloud_slots)``
    tuples — one per replica (scenario × policy × seed).  Model tables
    are padded to the max model count, pool arrays to the max slot
    count, signals to the max (ticks, edges) shape; policies become
    per-replica runtime :class:`PolicyParams`.  Policies must agree on
    ``adapt_window`` (an estimator buffer *shape*).
    """
    pols = [_resolve_policy(p) for _, p, _, _ in runs]
    windows = {p.adapt_window for p in pols}
    if len(windows) > 1:
        raise ValueError(
            f"build_fleet_batch: policies disagree on adapt_window "
            f"{sorted(windows)} — the estimator buffer is a compiled "
            f"shape, so one batch must share it")
    mmax = max(len(models) for models, _, _, _ in runs)
    smax = max(slots for _, _, _, slots in runs)
    emax = max(sig.arrive.shape[1] for _, _, sig, _ in runs)
    profs, states, cache = [], [], {}
    for (models, _, sig, slots), pol in zip(runs, pols):
        # lanes of the same (model table, pool, window) share one init
        # (ModelProfile is a frozen dataclass, so the full table is the key)
        key = (slots, pol.adapt_window, tuple(models))
        if key not in cache:
            prof = Profiles.build(models, pad_to=mmax)
            cache[key] = (prof, jax.vmap(
                lambda _, prof=prof: init_state(
                    prof, pol.adapt_window, slots, total_slots=smax))(
                jnp.arange(emax)))
        prof, state = cache[key]
        profs.append(prof)
        states.append(state)
    return FleetBatch(
        profiles=jax.tree.map(lambda *xs: jnp.stack(xs), *profs),
        params=jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[p.params() for p in pols]),
        state=jax.tree.map(lambda *xs: jnp.stack(xs), *states),
        signals=pad_signals([sig for _, _, sig, _ in runs], dt),
        coop_rounds=max((p.coop_max_transfers for p in pols
                         if p.cooperation), default=0))


def plan_buckets(runs, *, dt: float = 25.0
                 ) -> list[tuple[FleetBatch, tuple[int, ...]]]:
    """Shape-bucketed planner: exact-shape batches, one jit per bucket.

    Takes the same ``(models, policy, signals, cloud_slots)`` run list
    as :func:`build_fleet_batch`, but instead of padding every replica
    to the batch max shape, partitions the runs by exact
    ``(ticks, edges, models, coop_rounds, adapt_window)`` — within a
    bucket stacking is exact, so mixed-size sweeps (the ``*-COOP``
    registry case) stop paying max-shape padding, and peer-offload
    rounds compile only into the buckets that need them.  Each bucket
    compiles one program; the bounded :func:`_fleet_program` cache keeps
    bucket proliferation from retrace-leaking.

    Returns ``(batch, idxs)`` per bucket, where ``idxs`` maps the
    bucket's replica lanes back to positions in ``runs`` (lane ``k`` of
    the bucket's :func:`run_batch` result is run ``idxs[k]``).  Bucket
    results are bitwise identical to running the whole list through one
    padded :func:`build_fleet_batch` / :func:`run_batch` program —
    padding cells are exact no-ops by construction, so both equal the
    per-run :func:`run_fleet` loop.
    """
    buckets: dict = {}
    for i, run in enumerate(runs):
        models, policy, sig, _slots = run
        pol = _resolve_policy(policy)
        t, e, _m = sig.arrive.shape
        key = (t, e, len(models),
               pol.coop_max_transfers if pol.cooperation else 0,
               pol.adapt_window)
        bucket = buckets.setdefault(key, ([], []))
        bucket[0].append(run)
        bucket[1].append(i)
    return [(build_fleet_batch(rs, dt=dt), tuple(idxs))
            for rs, idxs in buckets.values()]


def run_batch(batch: FleetBatch, *, dt: float = 25.0,
              edge_frac: float = 0.62, cloud_frac: float = 0.80,
              mesh: Optional[jax.sharding.Mesh] = None,
              record_trace: bool = False,
              trace: Optional[TraceSpec] = None,
              donate: bool = False,
              chunk_ticks: Optional[int] = None):
    """Execute a heterogeneous :class:`FleetBatch` as one compiled program.

    Every replica — its own scenario shape, policy flags, model table and
    pool depth — runs under one jit; per-replica slices of the returned
    ``[R, E, …]`` state match the corresponding :func:`run_fleet` call
    exactly (padding is a no-op by construction).  A 2-D ``mesh`` shards
    the (replica, edge) grid; a 1-D mesh shards replicas only.  ``trace``
    (or the deprecated ``record_trace`` alias) returns a
    :class:`FleetResult` whose streams lead with the replica axis
    (``t_hat`` shaped ``[R, T, E, M]``); padded (tick, edge) cells record
    zero events, by the same masking that makes them state no-ops.
    ``donate=True`` hands the batch's state buffers to XLA for in-place
    carry updates (``batch.state`` itself stays valid — the program runs
    on a private copy); ``chunk_ticks`` replays the horizon in
    double-buffered windows.  Both knobs leave results bitwise unchanged.
    """
    tspec = resolve_spec(trace, record_trace)
    prof, pp, state, sig = (batch.profiles, batch.params, batch.state,
                            batch.signals)
    prog = FleetProgram(dt=dt, edge_frac=edge_frac, cloud_frac=cloud_frac,
                        coop_rounds=batch.coop_rounds, trace=tspec,
                        batched=True, hetero=True, donate=donate)
    if mesh is not None:
        prof = _shard_leading(prof, mesh, axes=1)
        pp = _shard_leading(pp, mesh, axes=1)
        state = _shard_leading(state, mesh, axes=2)
        sig = _shard_signals(sig, mesh)
    with _on_mesh(mesh):
        return prog.run(prof, pp, state, sig, chunk_ticks)


def simulate_fleet(models: list[ModelProfile], policy: str, *,
                   n_edges: int, drones_per_edge: int = 3,
                   duration_ms: float = 300_000.0, dt: float = 25.0,
                   edge_frac: float = 0.62, cloud_frac: float = 0.80,
                   cloud_slots: int = CLOUD_SLOTS,
                   theta_fn=None, bw_fn=None, seed: int = 0,
                   mesh: Optional[jax.sharding.Mesh] = None) -> EdgeState:
    """Simulate ``n_edges`` base stations under the paper's steady
    workload; returns stacked final states.  Scenario-driven runs (bursts,
    mobility, outages, …) go through :func:`run_fleet` with signals from
    :mod:`repro.scenarios.compile`."""
    signals = default_signals(len(models), n_edges=n_edges,
                              drones_per_edge=drones_per_edge,
                              duration_ms=duration_ms, dt=dt,
                              theta_fn=theta_fn, bw_fn=bw_fn, seed=seed)
    return run_fleet(models, policy, signals, dt=dt, edge_frac=edge_frac,
                     cloud_frac=cloud_frac, cloud_slots=cloud_slots,
                     mesh=mesh)
