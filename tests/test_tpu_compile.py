"""Compile the fleet's TPU paths for a described v5e, with no chip attached.

Interpret-mode kernel tests run the kernel body on the CPU; they cannot
see what Mosaic refuses (block shapes off the (8, 128) tiling, rank-1
outputs under ``vmap``).  These tests hand the TPU compiler the programs
the chip runs, placed on a device of a described ``v5e:2x2`` topology:

* the selection kernel (``kernels.sched_ops``) unbatched at several row
  counts and vmapped over fleets, as the tick step calls it;
* whole tick programs, whose ``platform_dependent`` selection must lower
  to the Mosaic kernel (``tpu_custom_call``) when compiled for a TPU.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.  The persistent compilation cache is off around the compiles
(an entry written for a described chip cannot be read back here).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.task import PASSIVE, TABLE1
from repro.kernels import sched_ops
from repro.scenarios import compile_registry_batch
from repro.sim.fleet_jax import (EDGE_CAP, FleetPolicy, FleetProgram,
                                 Profiles, default_signals)

MODELS = [TABLE1[n] for n in PASSIVE]
PHASES = ("resolve_cloud", "route_arrivals", "edge_execute", "gems_act")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler installed
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        jnp.shape(a), jnp.result_type(a), sharding=sharding), tree)


def _assert_kernel(compiled, n_sites: int = 1) -> None:
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= n_sites, \
        "selection did not lower to the Mosaic kernel"
    # the kernel keeps its name and scope, which a profile reports
    assert "%_argext_kernel" in hlo and "masked_argext" in hlo


@pytest.mark.parametrize("rows,n", [(3, 64), (8, 64), (16, EDGE_CAP),
                                    (1024, 64), (1, 1024)])
def test_selection_kernel_compiles_unbatched(one_chip, rows, n):
    spec = (jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows, n), bool, sharding=one_chip))
    _assert_kernel(jax.jit(sched_ops.masked_argmin).lower(*spec).compile())


@pytest.mark.parametrize("n_edges", [64, 1024])
def test_selection_kernel_compiles_vmapped_over_edges(one_chip, n_edges):
    # the tick step's per-edge call: a 1-D score row per edge, vmapped
    spec = (jax.ShapeDtypeStruct((n_edges, 64), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((n_edges, 64), bool, sharding=one_chip))
    fn = jax.jit(jax.vmap(sched_ops.masked_argmax))
    _assert_kernel(fn.lower(*spec).compile())


@pytest.mark.parametrize("policy,n_edges,sites", [("DEMS-A", 64, 1),
                                                  ("DEMS-A", 1024, 1),
                                                  ("DEMS-COOP", 256, 5)])
def test_tick_program_compiles_with_kernel(one_chip, policy, n_edges, sites):
    """A 64-tick chunk of the donated replay program; ``sites`` counts
    the selection call sites the policy compiles in (steal, plus export
    and three fleet-wide picks per peer-offload round)."""
    pol = FleetPolicy.from_name(policy)
    prog = FleetProgram.for_policy(pol, donate=True)
    prof = Profiles.build(MODELS)
    sig = default_signals(len(MODELS), n_edges=n_edges, duration_ms=1_600.0)
    compiled = prog.lower(
        _on(one_chip, prof), _on(one_chip, pol.params()),
        _on(one_chip, prog.init(prof, pol, n_edges)),
        _on(one_chip, sig)).compile()
    _assert_kernel(compiled, sites)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    # the compiled program keeps each tick phase's name scope, which a
    # profile reports as each operation's op_name
    ops = re.findall(r'op_name="([^"]+)"', compiled.as_text())
    for scope in PHASES + (("peer_offload",) if "COOP" in policy else ()):
        assert any(scope in re.split(r"[/()]", o) for o in ops), scope


def test_batched_sweep_program_compiles_with_kernel(one_chip):
    batch, _ = compile_registry_batch(("baseline", "rush-hour"),
                                      ("DEMS", "DEMS-COOP"), (0,),
                                      duration_ms=2_000.0)
    prog = FleetProgram(coop_rounds=batch.coop_rounds, batched=True,
                        hetero=True)
    compiled = prog.lower(*(_on(one_chip, t) for t in (
        batch.profiles, batch.params, batch.state, batch.signals))).compile()
    _assert_kernel(compiled, 5)
