"""The reduction from a profiler trace to device busy time and its parts.

A traced run records the measured window with ``jax.profiler``; the host
marks the window and what it was doing with ``bench.*`` annotations on
the same clock.  :func:`load` keeps, per device, the operations of its
``XLA Ops`` line (name, start, duration in ns) and a text of each
operation's metadata for name matching; :func:`Trace.from_json` reads the
same structure from a recorded fixture.  Everything else here is plain
interval arithmetic, so every PR reduces a trace the same way.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[tuple[str, int, int]]]
    meta: dict[str, str]
    host: list[tuple[str, int, int]]
    window: tuple[int, int]
    # busy time per device, computed once: every reader asks for it
    busy: dict = dataclasses.field(default_factory=dict, repr=False,
                                   compare=False)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with open(path) as f:
            d = json.load(f)
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   d["meta"], [tuple(e) for e in d["host"]],
                   tuple(d["window"]))


def load(logdir: str, window_span: str = "bench.window") -> Trace:
    """Read the ``.xplane.pb`` a ``jax.profiler`` trace left in ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, meta, host = {}, {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    ops.append((name, int(ev.start_ns), int(ev.duration_ns)))
                    if name not in meta:
                        meta[name] = " ".join(
                            str(v) for _, v in ev.stats if isinstance(v, str))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    win = [h for h in host if h[0] == window_span]
    if not win:
        raise ValueError(f"trace has no {window_span!r} annotation")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    return Trace(devices, meta, host, (lo, hi))


def _clip(events, lo: int, hi: int):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def union_ns(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_a, cur_b = 0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def busy_ns(tr: Trace, device: str) -> int:
    if device not in tr.busy:
        lo, hi = tr.window
        tr.busy[device] = union_ns(
            (a, b) for _, a, b in _clip(tr.devices[device], lo, hi))
    return tr.busy[device]


def busiest(tr: Trace) -> str | None:
    """The device with the most busy time in the window (None if none)."""
    if not tr.devices:
        return None
    return max(sorted(tr.devices), key=lambda d: busy_ns(tr, d))


def matches(tr: Trace, name: str, patterns) -> bool:
    text = name + " " + tr.meta.get(name, "")
    return any(re.search(p, text) for p in patterns)


def _matching(tr: Trace, device: str, patterns) -> set:
    """The distinct operation names that match: an operation runs many
    times under one name, so each name is matched once."""
    return {n for n in {e[0] for e in tr.devices[device]}
            if matches(tr, n, patterns)}


def matched_ns(tr: Trace, device: str, patterns) -> int:
    """Device time of the operations whose name or metadata matches any
    pattern (union of their intervals, so nesting counts once)."""
    lo, hi = tr.window
    hit = _matching(tr, device, patterns)
    return union_ns((a, b) for n, a, b in _clip(tr.devices[device], lo, hi)
                    if n in hit)


def matched_count(tr: Trace, device: str, patterns) -> int:
    lo, hi = tr.window
    hit = _matching(tr, device, patterns)
    return sum(1 for n, _, _ in _clip(tr.devices[device], lo, hi)
               if n in hit)


_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
# operations that only hold others: their time is their children's
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...), kind=kLoop`` -> ``%fusion.12
    fusion``: the instruction's name and opcode, with a custom call's
    target."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    out = f"{head} {m.group(1)}" if m else head
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{out} {t.group(1)}" if t else out


def top_ops(tr: Trace, device: str, k: int = 10):
    """The ``k`` operations with the most device time, ``[name, s]``,
    leaving out the control flow that only holds other operations."""
    lo, hi = tr.window
    by = {}
    for n, a, b in _clip(tr.devices[device], lo, hi):
        by[n] = by.get(n, 0) + (b - a)
    out = []
    for n, t in sorted(by.items(), key=lambda kv: -kv[1]):
        short = short_name(n)
        if short.split(" ")[1:2] and short.split(" ")[1] in CONTAINERS:
            continue
        out.append([short, t / 1e9])
        if len(out) == k:
            break
    return out


def idle_gaps(tr: Trace, device: str, k: int = 10):
    """The ``k`` longest idle gaps of the window, each named by the
    innermost ``bench.*`` host span at its midpoint, ``[name, s]``."""
    lo, hi = tr.window
    spans = sorted((a, b) for _, a, b in _clip(tr.devices[device], lo, hi))
    gaps, cur = [], lo
    for a, b in spans:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) // 2
        inside = [h for h in tr.host if h[1] <= mid < h[1] + h[2]
                  and h[0] != "bench.window"]
        name = min(inside, key=lambda h: h[2])[0] if inside else "host"
        out.append([name, (b - a) / 1e9])
    return out
