"""Profiling hooks: ``jax.profiler`` capture and compile accounting.

Two concerns, both about the *compiled program*, not the scheduler:

* :func:`profile_trace` — a context manager around
  ``jax.profiler.trace`` that captures a TensorBoard/Perfetto-readable
  device trace into a log directory (a profiler that refuses to start
  raises).
* :class:`CompileCounter` / :func:`fleet_compile_stats` — retrace
  accounting.  The fleet tick is policy-*generic*: every policy is
  runtime ``PolicyParams`` data, so one ``(dt, fractions, trace spec,
  layout)`` cell of :func:`repro.sim.fleet_jax._fleet_program` must
  trace **once** no matter how many policies run through it.  A leak of
  policy data into a static argument shows up here as extra traces —
  ``tests/conftest.py``'s ``compile_guard`` fixture turns that into a
  test failure.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax

# The monitoring event XLA fires once per backend compile.  Counting it
# sees through every cache layer (lru_cache, jit trace cache,
# persistent compilation cache misses).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def profile_trace(logdir: str, *, create_perfetto_trace: bool = False):
    """Capture a ``jax.profiler`` device trace into ``logdir``.

    View with TensorBoard's profile plugin or (with
    ``create_perfetto_trace=True``) the generated ``.perfetto-trace``
    file in ``ui.perfetto.dev``.  A profiler that refuses to start
    raises: a run that asked for a trace never goes on without one.
    """
    jax.profiler.start_trace(
        logdir, create_perfetto_trace=create_perfetto_trace)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()


class CompileCounter:
    """Count XLA backend compiles (and their wall time) in a scope.

    >>> with CompileCounter() as cc:
    ...     run_fleet(...)
    >>> cc.count, cc.total_secs

    Uses :mod:`jax.monitoring` duration events: an in-memory jit cache
    hit doesn't count; a persistent-cache hit counts, with the time it
    took to read the executable back.
    """

    def __init__(self) -> None:
        self.count = 0
        self.total_secs = 0.0

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.total_secs += duration

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)


@dataclasses.dataclass
class FleetCompileStats:
    """Snapshot of the policy-generic tick program's trace caches."""

    programs: int        # distinct (dt, fracs, tspec, layout) programs
    traces: int          # total jit traces across all of them
    max_traces_per_program: int
    capacity: int = 0    # bounded program-cache size (LRU eviction past it)
    evictions: int = 0   # programs evicted since the last reset

    @property
    def policy_generic(self) -> bool:
        """True iff no program traced twice.

        Valid verdict only when every program saw a single input shape
        (e.g. after :func:`reset_fleet_programs`, one workload, many
        policies) — shape changes legitimately retrace.  For
        shape-varied sessions, compare :attr:`traces` deltas instead
        (the ``compile_guard`` fixture's approach).
        """
        return self.max_traces_per_program <= 1


def fleet_compile_stats() -> FleetCompileStats:
    """Read the live ``_fleet_program`` cache: programs × jit traces.

    Each cached program is a ``jax.jit`` wrapper; its ``_cache_size()``
    is how many distinct argument structures (shapes/dtypes) traced
    through it.  Growth *without* a new input shape means some runtime
    input (usually a policy field) leaked into the static/trace-level
    signature.
    """
    from repro.sim import fleet_jax

    sizes = [prog._cache_size() for prog in fleet_jax._PROGRAM_REGISTRY]
    return FleetCompileStats(
        programs=len(sizes), traces=sum(sizes),
        max_traces_per_program=max(sizes, default=0),
        capacity=fleet_jax.FLEET_PROGRAM_CACHE_CAPACITY,
        evictions=fleet_jax._PROGRAM_EVICTIONS)


def reset_fleet_programs() -> None:
    """Drop all cached tick programs (test isolation for retrace guards)."""
    from repro.sim import fleet_jax

    fleet_jax._fleet_program.cache_clear()
    fleet_jax._PROGRAM_REGISTRY.clear()
    fleet_jax._PROGRAM_EVICTIONS = 0
