"""Compile-cache discipline of the shape-bucketed sweep planner.

Two properties keep metropolis-scale sweeps from drowning in XLA:

* **bucket → trace accounting** — a sweep over N distinct shape buckets
  pays exactly N jit traces (the tick program is policy-generic and
  shape-keyed, nothing else), and re-running the identical sweep pays
  zero;
* **bounded program cache** — ``_fleet_program`` is an LRU with capacity
  ``FLEET_PROGRAM_CACHE_CAPACITY``; a long-lived process churning
  through ad-hoc statics evicts instead of growing without bound, and
  the eviction count is observable via ``fleet_compile_stats``.
"""
import pytest

from repro.obs import prof
from repro.obs.trace import TraceSpec
from repro.sim import fleet_jax


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Count traces from zero and leave no fuzz-sized programs behind."""
    prof.reset_fleet_programs()
    yield
    prof.reset_fleet_programs()


def test_three_bucket_sweep_compiles_three_programs(compile_guard):
    from repro.scenarios import run_registry_sweep

    # baseline (1 edge, PASSIVE), rush-hour (2 edges, PASSIVE) and
    # roaming-vips (3 edges, ACTIVE) land in three distinct coop
    # buckets under GEMS-COOP — three exact shapes, three traces
    scenarios = ("baseline", "rush-hour", "roaming-vips")
    rows = run_registry_sweep(scenarios, ("GEMS-COOP",), (0,),
                              duration_ms=4_000.0, planner="bucketed")
    assert [r["scenario"] for r in rows] == list(scenarios)
    stats = prof.fleet_compile_stats()
    assert stats.traces == 3, (
        f"3-bucket sweep should trace exactly 3 programs, "
        f"got {stats.traces}")

    # the identical sweep again: every bucket hits the jit cache
    compile_guard.arm()
    rerun = run_registry_sweep(scenarios, ("GEMS-COOP",), (0,),
                               duration_ms=4_000.0, planner="bucketed")
    assert rerun == rows
    # compile_guard teardown asserts the rerun traced 0 new programs


def test_program_cache_evicts_beyond_capacity(monkeypatch):
    monkeypatch.setattr(fleet_jax, "FLEET_PROGRAM_CACHE_CAPACITY", 2)
    # building a program is cheap (the jit wrapper traces lazily), so
    # churning statics through a capacity-2 cache must evict the LRU
    # entry instead of growing without bound
    progs = [fleet_jax._fleet_program(dt, 0.62, 0.80, 0, TraceSpec(),
                                      False, False, False)
             for dt in (11.0, 13.0, 17.0)]
    stats = prof.fleet_compile_stats()
    assert stats.capacity == 2
    assert stats.programs <= 2
    assert stats.evictions >= 1
    # the newest entry survived and is returned by identity on re-request
    assert fleet_jax._fleet_program(17.0, 0.62, 0.80, 0, TraceSpec(),
                                    False, False, False) is progs[-1]
    # 11.0 was the LRU casualty: re-requesting it builds a fresh program
    assert fleet_jax._fleet_program(11.0, 0.62, 0.80, 0, TraceSpec(),
                                    False, False, False) is not progs[0]


def test_cache_clear_resets_registry_and_evictions():
    fleet_jax._fleet_program(19.0, 0.62, 0.80, 0, TraceSpec(),
                             False, False, False)
    assert prof.fleet_compile_stats().programs == 1
    prof.reset_fleet_programs()
    stats = prof.fleet_compile_stats()
    assert stats.programs == 0
    assert stats.traces == 0
    assert stats.evictions == 0


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_enable_compile_cache_places_the_persistent_cache(monkeypatch,
                                                         env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; otherwise
    the cache is the checkout's fixed ``.jax_cache/``.  Either way every
    program is cached, however fast it compiled."""
    import jax

    from repro.launch import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        where = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert where == str(compile_cache.CACHE_DIR)
            assert compile_cache.CACHE_DIR.parent.joinpath(
                "pyproject.toml").exists()
            assert jax.config.jax_compilation_cache_dir == where
        else:
            assert where == env_dir
            assert jax.config.jax_compilation_cache_dir \
                == was["jax_compilation_cache_dir"]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
