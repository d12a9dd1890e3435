"""The plain reference agrees with the program at a tiny size, and the
control (the reference in bfloat16) fails the committed limits."""
from __future__ import annotations

import json

import jax
import ml_dtypes
import numpy as np
import pytest

from benchkit import ROOT, SEED, traffic

from harness import check, gen
from repro.core.task import ModelProfile
from repro.sim import fleet_jax as fj

CFG = json.loads((ROOT / "bench/configs/metro-1024.json").read_text())
LIVE_CFG = json.loads((ROOT / "bench/configs/vip-fleet-27.json").read_text())
DT = CFG["scheduler"]["dt_ms"]


def program_outcome(policy: str, sig: dict, n_edges: int) -> dict:
    models = [ModelProfile(m["name"], m["beta"], m["deadline_ms"],
                           m["t_edge_ms"], m["t_cloud_ms"], m["cost_edge"],
                           m["cost_cloud"]) for m in CFG["models"]]
    final = fj.run_fleet(models, policy, fj.FleetSignals(**sig), dt=DT)
    g = jax.device_get(final)
    return dict(n_success=g.n_success, n_miss=g.n_miss, n_drop=g.n_drop,
                n_stolen=g.n_stolen, n_edge_exec=g.n_edge_exec,
                n_peer_out=g.n_peer_out, n_peer_in=g.n_peer_in,
                eq_depth=g.eq.valid.sum(-1), cq_depth=g.cq.valid.sum(-1))


@pytest.mark.parametrize("mix,n_edges", [("steady-3da", 6),
                                         ("hotspot-4da-2da", 16)])
def test_reference_equals_program(mix, n_edges):
    t = traffic(mix, horizon_ms=12_000.0)
    sig = jax.device_get(gen.replay_signals(gen.seed_key(SEED), t, n_edges,
                                            len(CFG["models"]), DT))
    got = program_outcome(t["policy"], sig, n_edges)
    want, arrived = check.ref_replay(CFG, t["policy"], [sig])
    assert check.ledger_gap(got, arrived) == 0
    assert check.mismatch_pct(got, want, arrived) == 0.0
    assert int(got["n_success"].sum()) > 0
    if t["policy"].endswith("-COOP"):
        assert int(got["n_peer_out"].sum()) > 0


@pytest.mark.parametrize("mix,n_edges", [("steady-3da", 6),
                                         ("hotspot-4da-2da", 16)])
def test_control_fails_the_replay_limits(mix, n_edges):
    t = traffic(mix, horizon_ms=12_000.0)
    sig = jax.device_get(gen.replay_signals(gen.seed_key(SEED), t, n_edges,
                                            len(CFG["models"]), DT))
    want, arrived = check.ref_replay(CFG, t["policy"], [sig])
    got, _ = check.ref_replay(CFG, t["policy"], [sig], ml_dtypes.bfloat16)
    limits = CFG["limits"]
    assert (check.ledger_gap(got, arrived) > limits["ledger_gap"]
            or check.mismatch_pct(got, want, arrived)
            > limits["mismatch_pct"])


def test_control_fails_the_live_limits():
    t = traffic("live-3da")
    drones, phases = gen.fleet_draw(gen.seed_key(SEED), t,
                                    LIVE_CFG["n_edges"], DT)
    period = gen.period_ticks(t, DT)
    sched = gen.live_schedule(drones, phases, period, len(LIVE_CFG["models"]))
    arrivals = [sched[k % period] for k in range(200)]
    want_rec, want, arrived = check.ref_live(LIVE_CFG, t["policy"], arrivals,
                                             SEED, 200)
    got_rec, got, _ = check.ref_live(LIVE_CFG, t["policy"], arrivals, SEED,
                                     200, ml_dtypes.bfloat16)
    limits = LIVE_CFG["limits"]
    assert check.record_mismatch_pct(got_rec, want_rec) \
        > limits["record_mismatch_pct"]
    assert check.mismatch_pct(got, want, arrived) > limits["mismatch_pct"]


@pytest.mark.parametrize("policy", ["DEMS-A", "DEMS-A-COOP"])
def test_stacked_runs_equal_runs_stepped_alone(policy):
    """Independent runs stacked in one reference, each run with its own
    FaaS slot count and its own peer exchange, end as each run stepped
    alone; the 2-slot runs end otherwise than with 16 slots."""
    runs, edges, slots = 3, 4, [2, 16, 2]
    t = traffic("hotspot-4da-2da", policy=policy, horizon_ms=12_000.0,
                drones_per_edge=3, hot_fraction=0.25, hot_drones_per_edge=6)
    sig = jax.device_get(gen.replay_signals(
        gen.seed_key(SEED), t, runs * edges, len(CFG["models"]), DT))
    stacked = check.reference(CFG, policy, runs * edges,
                              slots=np.repeat(slots, edges),
                              groups=np.repeat(np.arange(runs), edges))
    alone = [check.reference(CFG, policy, edges, slots=s) for s in slots]
    roomy = check.reference(CFG, policy, edges)           # 16 slots
    for k in range(sig["times"].shape[0]):
        x = {f: v[k] for f, v in sig.items()}
        x["now"] = x.pop("times")
        stacked.step(x)
        for r, ref in enumerate(alone + [roomy]):
            lo = (r % runs) * edges
            ref.step({f: v if np.ndim(v) == 0 else v[lo:lo + edges]
                      for f, v in x.items()})
    got = stacked.outcome()
    for f in check.FIELDS:
        want = np.concatenate([ref.outcome()[f] for ref in alone])
        np.testing.assert_array_equal(got[f], want, err_msg=f)
    assert any((alone[0].outcome()[f] != roomy.outcome()[f]).any()
               for f in check.FIELDS)
    if policy.endswith("-COOP"):
        assert (got["n_peer_out"].reshape(runs, edges).sum(1) > 0).all()
