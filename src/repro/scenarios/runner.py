"""Drive a compiled scenario through either simulator and merge results.

``run_scenario_oracle`` runs one discrete-event :class:`Simulator` per
edge site (each with its own θ trace, outage windows and speed-scaled
model table) and merges the per-edge :class:`Results`.
``run_scenario_fleet`` lowers the same spec to dense tick signals and runs
the vmapped/shardable JAX fleet simulator, optionally with cross-edge
peer offload (``FleetPolicy.cooperation`` / ``"<name>-COOP"``).
``run_scenario_fleet_batch`` sweeps one scenario over many seeds as a
single compiled program (one jit instead of R Python-loop jits).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core.schedulers import make_policy
from repro.scenarios.compile import (compile_exec_jitter, compile_fleet,
                                     compile_fleet_batch, compile_oracle)
from repro.scenarios.spec import ScenarioSpec
from repro.sim.engine import FleetOracle, ModelStats, Results, Simulator
from repro.sim.network import (CloudLatencyModel, EdgeLatencyModel,
                               TableCloudLatencyModel,
                               TableEdgeLatencyModel)


def merge_results(results: list[Results]) -> Results:
    """Fleet-wide totals: per-model stats summed across edge sites."""
    per_model: dict[str, ModelStats] = {}
    for r in results:
        for name, st in r.per_model.items():
            agg = per_model.setdefault(name, ModelStats())
            for f in dataclasses.fields(ModelStats):
                setattr(agg, f.name,
                        getattr(agg, f.name) + getattr(st, f.name))
    # duration = total edge-time so edge_utilization reads as fleet average
    return Results(policy=results[0].policy if results else "?",
                   duration=sum(r.duration for r in results),
                   per_model=per_model,
                   edge_busy=sum(r.edge_busy for r in results))


@dataclasses.dataclass
class OracleScenarioRun:
    spec: ScenarioSpec
    per_edge: list[Results]
    merged: Results


def run_scenario_oracle(spec: ScenarioSpec, policy: str, *,
                        edge_model: EdgeLatencyModel | None = None,
                        cloud_concurrency: int | None = None,
                        cloud_model_overrides: dict | None = None,
                        cloud_give_up_ms: float = float("inf"),
                        dt: float = 25.0,
                        **policy_overrides) -> OracleScenarioRun:
    """One event-driven Simulator per edge site.

    ``cloud_concurrency`` defaults to ``spec.cloud_concurrency`` (each
    edge's share of the bounded FaaS pool); ``cloud_model_overrides``
    replaces :class:`CloudLatencyModel` fields (e.g. ``sigma=1e-6`` for
    deterministic fleet-agreement comparisons) while the compiled θ and
    bandwidth traces stay attached.

    With ``spec.jitter`` set, both latency models become table-backed
    (:class:`~repro.sim.network.TableEdgeLatencyModel` /
    :class:`~repro.sim.network.TableCloudLatencyModel`) over the *same*
    per-(tick, model) sample tables the fleet simulator consumes as its
    ``exec_jit`` lane — same-sample fleet-vs-oracle comparisons.

    With ``spec.faults`` set, the compiled chaos lowering rides along:
    flood arrivals are already merged into each edge's stream, θ/bw
    traces carry the jamming and brownout overlays, partitions surface
    as per-edge zero-cold outage windows and edge crashes as
    ``edge_down_windows``.  ``cloud_give_up_ms`` bounds how long a
    parked cloud dispatch waits before being abandoned — pass the same
    value as the fleet side's ``FleetPolicy.cloud_give_up_ms`` for
    agreement runs.

    A ``*-COOP`` policy runs the per-edge simulators through the
    :class:`~repro.sim.engine.FleetOracle` lockstep wrapper (base policy
    on each edge + cross-edge peer offload between ``dt`` slices,
    mirroring the fleet's exchange); silo policies keep the independent
    per-edge loop.
    """
    coop = policy.endswith("-COOP")
    base_policy = policy[:-5] if coop else policy
    compiled = compile_oracle(spec)
    jit_tables = None
    if spec.jitter is not None:
        jit_tables = compile_exec_jitter(spec, dt)
        if edge_model is None:
            edge_model = TableEdgeLatencyModel(
                table=jit_tables[0], names=spec.model_names, dt=dt)
    sims: list[Simulator] = []
    for e, arrivals in enumerate(compiled.edge_arrivals):
        shaping = dict(latency_at=compiled.theta_fns[e],
                       bandwidth_at=compiled.bw_fns[e])
        if jit_tables is not None:
            cloud_model = TableCloudLatencyModel(
                table=jit_tables[1], names=spec.model_names, dt=dt,
                **shaping, **(cloud_model_overrides or {}))
        else:
            cloud_model = CloudLatencyModel(
                **shaping, **(cloud_model_overrides or {}))
        sims.append(Simulator(
            make_policy(base_policy, **policy_overrides), arrivals,
            spec.duration_ms,
            cloud_concurrency=spec.cloud_concurrency
            if cloud_concurrency is None else cloud_concurrency,
            edge_model=edge_model, cloud_model=cloud_model,
            cloud_outages=compiled.edge_outages[e]
            if compiled.edge_outages is not None else compiled.outages,
            edge_down_windows=compiled.crashes[e]
            if compiled.crashes is not None else (),
            cloud_give_up_ms=cloud_give_up_ms,
            seed=spec.seed + e))
    if coop:
        from repro.sim.fleet_jax import FleetPolicy
        fp = FleetPolicy.from_name(policy)
        per_edge = FleetOracle(
            sims, spec.duration_ms, dt=dt, slack_ms=fp.coop_slack_ms,
            max_transfers=fp.coop_max_transfers).run()
    else:
        per_edge = [sim.run() for sim in sims]
    return OracleScenarioRun(spec=spec, per_edge=per_edge,
                             merged=merge_results(per_edge))


def run_scenario_fleet(spec: ScenarioSpec, policy, *, dt: float = 25.0,
                       edge_frac: float = 0.62, cloud_frac: float = 0.80,
                       mesh=None, record_trace: bool = False, trace=None):
    """The scenario through the JAX fleet simulator (stacked EdgeState).

    The spec's ``cloud_concurrency`` becomes each edge's finite
    ``cloud_slots`` pool, matching the oracle path slot for slot.
    ``trace`` (a :class:`repro.obs.trace.TraceSpec`; ``record_trace`` is
    the deprecated ``TraceSpec(t_hat=True)`` alias) returns a
    ``FleetResult`` carrying the requested flight-recorder streams —
    per-tick adapted-t̂ (``[T, E, M]``, Fig. 12-style adaptation
    dynamics) and/or decision counters.
    """
    from repro.sim.fleet_jax import run_fleet

    signals = compile_fleet(spec, dt)
    return run_fleet(spec.models, policy, signals, dt=dt,
                     edge_frac=edge_frac, cloud_frac=cloud_frac,
                     cloud_slots=spec.cloud_concurrency, mesh=mesh,
                     record_trace=record_trace, trace=trace)


def stream_scenario_fleet(spec: ScenarioSpec, policy, *, dt: float = 25.0,
                          window_ticks: int = 16, edge_frac: float = 0.62,
                          cloud_frac: float = 0.80, trace=None):
    """The scenario through the *online* control plane, window-by-window.

    Compiles the same dense signals as :func:`run_scenario_fleet`, then
    feeds them through a :class:`repro.serve.controller.FleetController`
    in ``window_ticks`` chunks via its replay bridge
    (:meth:`~repro.serve.controller.FleetController.step_signals`).
    Returns the controller; its ``state`` is the streamed final
    :class:`~repro.sim.fleet_jax.EdgeState`.
    """
    from repro.obs.trace import TraceSpec
    from repro.serve.controller import FleetController
    from repro.sim.fleet_jax import slice_signals

    sig = compile_fleet(spec, dt)
    ctl = FleetController(
        spec.models, policy, n_edges=spec.n_edges, dt=dt,
        window_ticks=window_ticks, cloud_slots=spec.cloud_concurrency,
        edge_frac=edge_frac, cloud_frac=cloud_frac,
        trace=TraceSpec() if trace is None else trace)
    n_ticks = int(sig.times.shape[0])
    for lo in range(0, n_ticks, window_ticks):
        ctl.step_signals(slice_signals(sig, lo, min(lo + window_ticks,
                                                    n_ticks)))
    return ctl


def assert_streaming_equivalence(spec: ScenarioSpec, policy, *,
                                 dt: float = 25.0, window_ticks: int = 16
                                 ) -> dict[str, float]:
    """Replay-vs-streaming bitwise check (the equivalence test hook).

    Runs the scenario both ways — one :func:`run_scenario_fleet` replay
    call and a :class:`~repro.serve.controller.FleetController` stepping
    the identical signals window-by-window — and raises
    ``AssertionError`` naming the diverging ``EdgeState`` fields unless
    every leaf is bit-for-bit equal.  Returns the (shared) summary.
    """
    from repro.sim.fleet_jax import EdgeState

    ref = run_scenario_fleet(spec, policy, dt=dt)
    ctl = stream_scenario_fleet(spec, policy, dt=dt,
                                window_ticks=window_ticks)
    bad = [name for name, a, b in zip(EdgeState._fields, ref, ctl.state)
           if not all(np.array_equal(np.asarray(x), np.asarray(y))
                      for x, y in zip(jax.tree.leaves(a),
                                      jax.tree.leaves(b)))]
    if bad:
        raise AssertionError(
            f"streaming EdgeState diverged from replay in fields {bad} "
            f"({spec.name!r}, policy {policy!r}, "
            f"window_ticks={window_ticks})")
    return fleet_summary(ctl.state)


def run_scenario_fleet_batch(spec: ScenarioSpec, policy,
                             seeds: tuple[int, ...], *, dt: float = 25.0,
                             edge_frac: float = 0.62,
                             cloud_frac: float = 0.80, mesh=None,
                             record_trace: bool = False, trace=None):
    """One scenario × many seeds as one compiled fleet program.

    Returns a stacked final EdgeState with leading ``[R, E]`` axes;
    use :func:`fleet_summary_batch` for per-seed metrics.  ``trace`` /
    ``record_trace`` switch to a ``FleetResult`` with replica-leading
    streams (``t_hat`` shaped ``[R, T, E, M]``).
    """
    from repro.sim.fleet_jax import run_fleet_batch

    signals = compile_fleet_batch(spec, tuple(seeds), dt)
    return run_fleet_batch(spec.models, policy, signals, dt=dt,
                           edge_frac=edge_frac, cloud_frac=cloud_frac,
                           cloud_slots=spec.cloud_concurrency, mesh=mesh,
                           record_trace=record_trace, trace=trace)


def run_registry_sweep(scenarios=None, policies=("DEMS",), seeds=(0,), *,
                       dt: float = 25.0, duration_ms: float | None = None,
                       mesh=None, trace=None, planner: str = "bucketed",
                       donate: bool = False) -> list[dict]:
    """Scenarios × policies × seeds as compiled sweep programs.

    ``planner`` picks the lowering — both produce bitwise-identical
    rows (the fuzz harness in ``tests/test_fuzz_scenarios.py`` holds
    them to it):

    * ``"bucketed"`` (default) — the shape-bucketed multi-program
      planner: :func:`repro.scenarios.compile.compile_registry_groups`
      partitions the sweep into exact-shape buckets
      (:func:`repro.sim.fleet_jax.plan_buckets`), one jit per bucket,
      zero padding.  With ``mesh="auto"`` each bucket's replica axis
      fans over the largest dividing device count; an explicit mesh
      shards every bucket's (replica, edge) grid.
    * ``"padded"`` — the single max-shape padded program
      (:func:`repro.scenarios.compile.compile_registry_batch` +
      one :func:`repro.sim.fleet_jax.run_batch`): the reference baseline
      the bucketed planner is benchmarked and parity-checked against
      (``scaling`` section of ``BENCH_fleet.json``).

    ``scenarios`` accepts registry names and/or ad-hoc
    :class:`~repro.scenarios.spec.ScenarioSpec` instances.  ``donate``
    compiles the sweep programs with their carry buffers donated
    (in-place state updates — same rows, see
    :class:`~repro.sim.fleet_jax.FleetProgram`).  Returns one summary
    dict per run, tagged with its (scenario, policy, seed), in sweep
    order.

    ``trace`` (a :class:`repro.obs.trace.TraceSpec`) threads the flight
    recorder through the sweep: each row dict then also carries a
    ``"trace"`` :class:`~repro.sim.fleet_jax.FleetResult` whose streams
    are re-stacked to that run's own ``[T, E, …]`` layout (lanes of the
    edge-flattened lowering concatenated back along the edge axis; under
    the padded planner the model axis stays padded to the batch maximum,
    padded models simply never count).
    """
    from repro.launch.mesh import make_mesh
    from repro.scenarios.compile import (compile_registry_batch,
                                         compile_registry_groups)
    from repro.sim.fleet_jax import FleetResult, run_batch

    traced = trace is not None and trace.enabled

    def summarize(res, rows):
        final = res.final if traced else res
        out = []
        for row in rows:
            # a run's lanes are its replicas: one for a padded multi-edge
            # batch, one per edge under the edge-flattened lowering —
            # re-stack them into the run's [E, …] state so fleet_summary
            # reduces the per-edge values exactly as the run_fleet path
            # would
            def restack(tree, axis=0):
                parts = [jax.tree.map(lambda a, i=i: a[i], tree)
                         for i in row.lanes]
                return parts[0] if len(parts) == 1 else jax.tree.map(
                    lambda *xs: np.concatenate(
                        [np.asarray(x) for x in xs], axis=axis), *parts)
            state = restack(final)
            d = dict(scenario=row.scenario, policy=row.policy,
                     seed=row.seed, **fleet_summary(state))
            if traced:
                # trace streams are [T, E, …]: lanes rejoin on the edge
                # axis
                d["trace"] = FleetResult(
                    final=state, t_hat=restack(res.t_hat, axis=1),
                    counters=restack(res.counters, axis=1))
            out.append(d)
        return out

    auto = isinstance(mesh, str) and mesh == "auto"

    def auto_mesh(batch):
        r = int(batch.signals.arrive.shape[0])
        n = max(d for d in range(1, jax.device_count() + 1) if r % d == 0)
        return make_mesh((n,), ("replica",)) if n > 1 else None

    if planner == "bucketed":
        by_key = {}
        for batch, rows in compile_registry_groups(
                scenarios, policies, seeds, dt=dt, duration_ms=duration_ms):
            # one host transfer per bucket: the per-row lane slicing in
            # summarize would otherwise issue a device gather per leaf
            # per run (slow when the replica axis is sharded)
            res = jax.device_get(run_batch(
                batch, dt=dt, mesh=auto_mesh(batch) if auto else mesh,
                trace=trace, donate=donate))
            for d in summarize(res, rows):
                by_key[d["scenario"], d["policy"], d["seed"]] = d
        from repro.scenarios.registry import names
        order = tuple(sc if isinstance(sc, str) else sc.name
                      for sc in scenarios) if scenarios is not None \
            else names()
        return [by_key[sc, pol, seed]
                for sc in order for pol in policies for seed in seeds]
    if planner != "padded":
        raise ValueError(f"unknown planner {planner!r}; "
                         f"choose 'bucketed' or 'padded'")

    batch, rows = compile_registry_batch(scenarios, policies, seeds,
                                         dt=dt, duration_ms=duration_ms)
    if auto:
        mesh = auto_mesh(batch)
    res = jax.device_get(run_batch(batch, dt=dt, mesh=mesh, trace=trace,
                                   donate=donate))
    return summarize(res, rows)


def fleet_summary(final) -> dict[str, float]:
    """Scalar fleet-level metrics from a stacked final EdgeState."""
    success = int(np.asarray(final.n_success).sum())
    miss = int(np.asarray(final.n_miss).sum())
    drop = int(np.asarray(final.n_drop).sum())
    settled = max(success + miss + drop, 1)
    return dict(
        completed=success, missed=miss, dropped=drop,
        completion_rate=success / settled,
        qos_utility=float(np.asarray(final.qos_utility).sum()),
        qoe_utility=float(np.asarray(final.qoe_utility).sum()),
        stolen=int(np.asarray(final.n_stolen).sum()),
        peer_offloaded=int(np.asarray(final.n_peer_out).sum()))


def fleet_summary_batch(final) -> list[dict[str, float]]:
    """Per-replica summaries from a ``run_fleet_batch`` final state."""
    n_replicas = np.asarray(final.qos_utility).shape[0]
    return [fleet_summary(jax.tree.map(lambda a: a[r], final))
            for r in range(n_replicas)]
