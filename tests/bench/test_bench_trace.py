"""The trace reduction on small slices of a recorded chip trace.

Both fixtures are cut from one traced ``metro1024-steady-demsa`` window
on a TPU v5e: ``trace_steady_start.json`` is its first 6.2 ms, where the
host dispatches small programs with the device idle between them;
``trace_steady.json`` is 3 ms inside the tick program around the six
steal selections of one tick.  Each sum the reduction gives is checked
against a brute-force count on a nanosecond timeline, and against the
value recorded when the fixture was cut.
"""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from benchkit import ROOT, bench_run

from harness import trace as T

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"


def reader(name: str):
    R = bench_run()
    return R.load_reader(ROOT / "bench" / "metrics" / f"{name}.py")


def timeline(tr: T.Trace, device: str, keep=lambda name: True):
    """Busy nanoseconds of the window, one boolean per nanosecond."""
    lo, hi = tr.window
    busy = np.zeros(hi - lo, bool)
    for name, s, d in tr.devices[device]:
        if keep(name):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                busy[a - lo:b - lo] = True
    return busy


def load(name: str) -> T.Trace:
    return T.Trace.from_json(str(FIX / name))


FIXTURES = ["trace_steady_start.json", "trace_steady.json"]
# recorded when the fixtures were cut: (busy ns, window ns, idle gaps)
RECORDED = {"trace_steady_start.json": (1626611, 6200000, 37),
            "trace_steady.json": (3000000, 3000000, 0)}


@pytest.mark.parametrize("name", FIXTURES)
def test_busy_and_idle_match_a_brute_force_timeline(name):
    tr = load(name)
    for dev in tr.devices:
        assert T.busy_ns(tr, dev) == int(timeline(tr, dev).sum())
    top = T.busiest(tr)
    lo, hi = tr.window
    idle = reader("device_idle_pct.replay").read(dict(trace=tr))
    assert idle == pytest.approx(
        100.0 * (1 - timeline(tr, top).sum() / (hi - lo)))
    gaps = T.idle_gaps(tr, top, k=1000)
    assert sum(g[1] for g in gaps) * 1e9 == pytest.approx(
        (hi - lo) - T.busy_ns(tr, top), abs=1)
    assert (T.busy_ns(tr, top), hi - lo, len(gaps)) == RECORDED[name]


def test_idle_gaps_are_named_by_the_host_span():
    tr = load("trace_steady_start.json")
    gaps = T.idle_gaps(tr, T.busiest(tr))
    assert len(gaps) == 10
    assert {g[0] for g in gaps} == {"bench.issue"}
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_kernel_time_on_the_one_chip_slice():
    tr = load("trace_steady.json")
    dev = T.busiest(tr)
    pats = reader("argext_busy_pct").PATTERNS
    got = T.matched_ns(tr, dev, pats)
    want = int(timeline(tr, dev, lambda n: T.matches(tr, n, pats)).sum())
    assert got == want == 1439930
    assert T.matched_count(tr, dev, pats) == 6      # one per substep
    share = reader("argext_busy_pct").read(dict(trace=tr))
    assert share == pytest.approx(100.0 * want / T.busy_ns(tr, dev))


def test_roofline_reads_nothing_without_the_kernel():
    tr = load("trace_steady_start.json")
    layer = dict(ticks=2, n_edges=1024, substeps=6, cloud_cap=64,
                 edge_cap=32, coop=True, coop_rounds=2)
    peak = {"hbm_bytes_per_s": 819e9}
    assert reader("argext_roofline").read(
        dict(trace=tr, layer=layer, peak=peak)) is None


def test_roofline_bytes_from_the_call_shapes():
    mod = reader("argext_roofline")
    steady = dict(n_edges=1024, substeps=6, cloud_cap=64, edge_cap=32,
                  coop=False, coop_rounds=2)
    # six steal selections over 1024 rows of 64: scores f32 + mask bool
    # read, index i32 + value f32 written per row
    assert mod.bytes_per_tick(steady) == 6 * 1024 * (64 * 5 + 8)
    coop = dict(steady, coop=True)
    assert mod.bytes_per_tick(coop) == mod.bytes_per_tick(steady) + 2 * (
        3 * (1024 * 5 + 8) + (32 * 5 + 8))


def test_short_names():
    assert T.short_name(
        '%b.7 = (s32[8]{0}) custom-call(f32[8]{0} %p), '
        'custom_call_target="tpu_custom_call"') == \
        "%b.7 custom-call tpu_custom_call"
    assert T.short_name("%f.1 = f32[8]{0:T(8)S(1)} fusion(f32[8] %x), "
                        "kind=kLoop") == "%f.1 fusion"


def test_selection_patterns_leave_out_other_kernels():
    """Another Pallas kernel (a gather, say) is a ``tpu_custom_call`` too,
    with other results; the selection's readers do not count it."""
    pats = reader("argext_busy_pct").PATTERNS
    assert pats == reader("argext_roofline").PATTERNS
    tr = load("trace_steady.json")
    sel = [n for n in {e[0] for e in tr.devices[T.busiest(tr)]}
           if T.matches(tr, n, pats)]
    assert len(sel) == 1 and 'custom_call_target="tpu_custom_call"' in sel[0]
    other = ('%gather.3 = f32[1024,64]{1,0:T(8,128)} custom-call('
             'f32[1024,64]{1,0:T(8,128)} %p.1, s32[1024,6]{1,0} %p.2), '
             'custom_call_target="tpu_custom_call"')
    pair = ('%pair.4 = (f32[1024,64]{1,0}, s32[1024,64]{1,0}) custom-call('
            'f32[1024,64]{1,0} %p.1), custom_call_target="tpu_custom_call"')
    for name in (other, pair):
        assert not T.matches(tr, name, pats)
