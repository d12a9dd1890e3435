"""The program's own spans and scopes, read from a profile.

``harness.program_trace`` reads what ``harness.trace`` leaves out: the
program's ``fleet.*`` host spans and each device operation's ``op_name``
path.  A traced controller on the CPU checks the spans the program
records; a hand-encoded profile checks the wire reader; two slices of a
traced chip run check the interval sums against brute-force nanosecond
timelines, and against the values recorded when they were cut.
"""
from __future__ import annotations

import glob
import os
import statistics

import numpy as np
import pytest

from test_bench_trace import FIX, FIXTURES, reader, timeline

from harness import program_trace as P
from harness import trace as T


def load(name: str) -> P.ProgramTrace:
    return P.ProgramTrace.from_json(str(FIX / name))


# what each existing reader reads on the fixtures as a plain ``Trace``:
# the program's spans and scopes leave them as they are
LAYER = dict(ticks=2, n_edges=1024, substeps=6, cloud_cap=64, edge_cap=32,
             coop=False, coop_rounds=2, ctl_step_ms=[25.0, 26.0, 24.0],
             ctl_host_ms=[17.0, 18.5, 16.0])
BEFORE = {
    "trace_steady_start.json": {
        "argext_busy_pct": None, "argext_roofline": None,
        "device_ms_per_tick": 0.8133055,
        "device_idle_pct.replay": 73.76433870967742,
        "device_idle_pct.live": 73.76433870967742,
        "ctl_host_ms_p50": 17.0, "ctl_step_ms_p50": 25.0},
    "trace_steady.json": {
        "argext_busy_pct": 47.99766666666667,
        "argext_roofline": 0.34176671540987863,
        "device_ms_per_tick": 1.5, "device_idle_pct.replay": 0.0,
        "device_idle_pct.live": 0.0, "ctl_host_ms_p50": 17.0,
        "ctl_step_ms_p50": 25.0},
}


@pytest.mark.parametrize("name,metric", [
    (f, m) for f in FIXTURES for m in BEFORE[f]])
def test_existing_readers_read_as_before(name, metric):
    ctx = dict(layer=LAYER, peak={"hbm_bytes_per_s": 819e9},
               setup_compile_s=1.5, chips=1)
    tr = load(name)
    assert tr.spans == [] and tr.scopes == {}
    got = reader(metric).read(dict(ctx, trace=tr))
    plain = reader(metric).read(
        dict(ctx, trace=T.Trace.from_json(str(FIX / name))))
    want = BEFORE[name][metric]
    assert got == plain
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


@pytest.mark.parametrize("name,metric", [
    (f.name, m.stem) for f in sorted(FIX.glob("trace_steady*.json"))
    for m in sorted((FIX.parents[2] / "bench" / "metrics").glob("*.py"))])
def test_every_reader_reads_alike_from_either_loader(name, metric):
    """``bench/run.py`` loads a traced run with ``program_trace.load``:
    every reader reads from its ``ProgramTrace`` what it reads from the
    plain ``Trace``, on every recorded fixture."""
    ctx = dict(layer=LAYER, peak={"hbm_bytes_per_s": 819e9},
               setup_compile_s=1.5, chips=1)
    got = reader(metric).read(dict(ctx, trace=load(name)))
    plain = reader(metric).read(
        dict(ctx, trace=T.Trace.from_json(str(FIX / name))))
    assert got == plain


@pytest.fixture(scope="module")
def controller_trace(tmp_path_factory):
    """A 4-edge controller stepped on the CPU under the profiler: six
    decision windows of four ticks, polled every tick."""
    import jax
    from repro.scenarios.registry import get
    from repro.serve.controller import FleetController

    models = get("baseline").models
    ctl = FleetController(models, "DEMS-A", n_edges=4, window_ticks=4)
    dt = ctl.dt

    def feed(lo, hi):
        for k in range(lo, hi):
            for e in range(4):
                ctl.submit(k * dt, e, (k + e) % len(models))
            ctl.poll(k * dt)

    feed(0, 8)          # compiles the window program outside the trace
    logdir = str(tmp_path_factory.mktemp("ctl-trace"))
    n0 = ctl.windows_run
    jax.profiler.start_trace(logdir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            feed(8, 32)
    finally:
        jax.profiler.stop_trace()
    xplane = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                       recursive=True)[0]
    return P.load(logdir), ctl.windows_run - n0, xplane


@pytest.mark.parametrize("inner", ["fleet.emit", "fleet.dispatch",
                                   "fleet.wait", "fleet.record"])
def test_controller_spans_nest_in_poll(controller_trace, inner):
    tr, windows, _ = controller_trace
    assert windows == 6
    polls = P.window_spans(tr, "fleet.poll")
    assert len(polls) == 24                       # one a tick
    spans = P.window_spans(tr, inner)
    assert len(spans) == windows
    stepping = [p for p in polls
                if any(p[1] <= s[1] and s[1] + s[2] <= p[1] + p[2]
                       for s in spans)]
    assert len(stepping) == windows               # one window a poll
    # a window's spans run in order inside its poll
    for p in stepping:
        inside = sorted((s for s in tr.spans if s[0] != "fleet.poll"
                         and p[1] <= s[1] and s[1] + s[2] <= p[1] + p[2]),
                        key=lambda s: s[1])
        assert [s[0] for s in inside] == ["fleet.emit", "fleet.dispatch",
                                          "fleet.wait", "fleet.record"]


def test_program_trace_keeps_what_the_plain_loader_reads(controller_trace):
    tr, _, xplane = controller_trace
    plain = T.load(os.path.dirname(xplane))
    assert (tr.devices, tr.meta, tr.host, tr.window) == (
        plain.devices, plain.meta, plain.host, plain.window)
    assert not any(h[0].startswith(P.PROGRAM_SPANS) for h in plain.host)


def _pb(*fields) -> bytes:
    """A protobuf message: ``(field, int | str | bytes)`` pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


OP = "%fusion.7 = f32[1024]{0} fusion(f32[1024,64]{1,0} %p), kind=kLoop"


@pytest.mark.parametrize("by_ref", [False, True])
def test_op_names_read_from_event_metadata(tmp_path, by_ref):
    """A TPU profile keeps an operation's ``op_name`` in the ``tf_op``
    stat of its event metadata, as text or as a reference to a stat
    metadata's name; the wire reader finds it on device planes only and
    skips the event lines."""
    from harness import xspace

    path = "jit(run)/while/body/vmap(route_arrivals)/while/add"
    stat_md = [(5, _pb((1, 1), (2, _pb((1, 1), (2, "tf_op"))))),
               (5, _pb((1, 9), (2, _pb((1, 9), (2, path + ":")))))]
    tf_op = (7, 9) if by_ref else (5, path + ":")
    event_md = (4, _pb((1, 3), (2, _pb((1, 3), (2, OP),
                                       (5, _pb((1, 1), tf_op))))))
    line = (3, _pb((1, 0), (2, "XLA Ops"), (4, _pb((1, 3), (2, 10), (3, 5)))))
    device = _pb((1, 1), (2, "/device:TPU:0"), line, event_md, *stat_md)
    host = _pb((1, 2), (2, "/host:CPU"), event_md, *stat_md)
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_pb((1, device), (1, host)))
    got = xspace.event_metadata_strings(str(f), T.DEVICE_PLANE.match)
    assert got == {"/device:TPU:0": {OP: {"tf_op": path + ":"}}}
    assert P.op_name(got["/device:TPU:0"][OP]) == path
    assert "route_arrivals" in P.scope_components(path)


@pytest.mark.parametrize("path,scope,inside", [
    ("jit(run)/while/body/closed_call/vmap(route_arrivals)/while/add",
     "route_arrivals", True),
    ("jit(run)/while/body/vmap(edge_execute)/masked_argext/cond",
     "masked_argext", True),
    ("jit(run)/while/body/closed_call/vmap(edge_execute)/lt",
     "execute", False),
    ("", "gems_act", False)])
def test_scope_components(path, scope, inside):
    assert (scope in P.scope_components(path)) is inside


# Two slices of one traced ``metro1024-steady-demsa`` window on a TPU v5e,
# recorded with the program's scopes and spans: ``trace_steady_phases``
# is 7.6 ms across a tick boundary (the end of the routing loop, edge
# execute with its six selections, GEMS, the start of the next tick's
# cloud resolve); ``trace_steady_spans`` is the window's first 10 ms,
# where the host copies the donated state inside ``fleet.copy_state``
# while the device idles between small programs.
PHASED, SPANNED = "trace_steady_phases.json", "trace_steady_spans.json"
PHASES = ("resolve_cloud", "route_arrivals", "edge_execute", "gems_act")
# recorded when the fixtures were cut (ns)
SCOPED_NS = {"resolve_cloud": 616172, "route_arrivals": 131617,
             "edge_execute": 2827585, "gems_act": 1428751,
             "masked_argext": 1463068}
IDLE_UNDER_SPANS_NS = {PHASED: 0, SPANNED: 8115801,
                       "trace_steady_start.json": 0, "trace_steady.json": 0}


def in_scope(tr: P.ProgramTrace, scope: str):
    return lambda name: scope in P.scope_components(tr.scopes.get(name, ""))


@pytest.mark.parametrize("scope", [*PHASES, "masked_argext"])
def test_scoped_time_matches_a_brute_force_timeline(scope):
    tr = load(PHASED)
    dev = T.busiest(tr)
    want = int(timeline(tr, dev, in_scope(tr, scope)).sum())
    assert P.scoped_ns(tr, dev, scope) == want == SCOPED_NS[scope]


def test_phases_together_match_a_brute_force_timeline():
    tr = load(PHASED)
    dev = T.busiest(tr)
    hit = [in_scope(tr, p) for p in PHASES]
    want = int(timeline(tr, dev, lambda n: any(h(n) for h in hit)).sum())
    assert P.scoped_ns(tr, dev, PHASES) == want


@pytest.mark.parametrize("name", list(IDLE_UNDER_SPANS_NS))
def test_idle_under_spans_matches_a_brute_force_timeline(name):
    tr = load(name)
    dev = T.busiest(tr)
    lo, hi = tr.window
    inside = np.zeros(hi - lo, bool)
    for span, s, d in tr.spans:
        a, b = max(s, lo), min(s + d, hi)
        if span.startswith("fleet.") and b > a:
            inside[a - lo:b - lo] = True
    want = int((inside & ~timeline(tr, dev)).sum())
    assert P.idle_under_spans_ns(tr, dev) == want == IDLE_UNDER_SPANS_NS[name]


def test_tick_phase_shares_sum_to_the_busy_time():
    """Each operation has one ``op_name``, so the four phases and the
    rest cover the busy time once; the selection is inside edge execute."""
    tr = load(PHASED)
    dev = T.busiest(tr)
    busy = T.busy_ns(tr, dev)
    shares = {p: 100.0 * P.scoped_ns(tr, dev, p) / busy for p in PHASES}
    rest = 100.0 * (busy - P.scoped_ns(tr, dev, PHASES)) / busy
    assert sum(shares.values()) + rest == pytest.approx(100.0, abs=0.5)
    assert shares["edge_execute"] == pytest.approx(100.0 * 2827585 / 7600000)
    argext = reader("argext_busy_pct").read(dict(trace=tr))
    assert argext < shares["edge_execute"]


def test_host_idle_share_on_the_spanned_slice():
    tr = load(SPANNED)
    lo, hi = tr.window
    got = 100.0 * P.idle_under_spans_ns(tr, T.busiest(tr)) / (hi - lo)
    assert got == pytest.approx(100.0 * 8115801 / 10_000_000)


@pytest.mark.parametrize("name,scope", [
    (f, s) for f in FIXTURES for s in [*PHASES, "masked_argext"]])
def test_nothing_is_scoped_without_scopes_or_spans(name, scope):
    """A profile recorded before the program named its phases and spans
    attributes nothing to them."""
    tr = load(name)
    dev = T.busiest(tr)
    assert P.scoped_ns(tr, dev, scope) == 0
    assert P.window_spans(tr, "fleet.poll") == []


@pytest.mark.parametrize("span", ["fleet.emit", "fleet.record",
                                  "fleet.dispatch"])
def test_window_spans_start_inside_the_window(span):
    tr = P.ProgramTrace({}, {}, [], (1_000, 100_000_000),
                        spans=[(span, 500, 9_000_000),      # starts before
                               (span, 2_000, 3_000_000),
                               (span, 9_000_000, 1_000_000),
                               (span, 20_000_000, 2_000_000),
                               ("fleet.poll", 3_000, 50_000_000)])
    got = P.window_spans(tr, span)
    assert [s[1] for s in got] == [2_000, 9_000_000, 20_000_000]
    assert statistics.median(d for _, _, d in got) == 2_000_000
