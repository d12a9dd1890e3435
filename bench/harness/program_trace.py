"""The program's own spans and scopes in a profiler trace.

:func:`harness.trace.load` keeps the host's ``bench.*`` spans and each
device operation's name and event stats.  The program marks more than
that: its host work with ``jax.profiler.TraceAnnotation`` spans named
``fleet.*``, and each tick phase with a ``jax.named_scope``, which the
profile keeps as the operation's ``op_name`` path (the ``tf_op`` stat of
its event metadata, which :mod:`harness.xspace` reads).  :func:`load`
reads the same profile as :func:`harness.trace.load` and adds both, as a
:class:`ProgramTrace` that every function of :mod:`harness.trace`
accepts; :meth:`ProgramTrace.from_json` reads a recorded fixture.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

from harness import trace as T
from harness import xspace

# the program's own host spans (``jax.profiler.TraceAnnotation``)
PROGRAM_SPANS = "fleet."
# the event-metadata stat that carries an operation's ``op_name``
OP_NAME_STAT = "tf_op"


@dataclasses.dataclass
class ProgramTrace(T.Trace):
    # the program's ``fleet.*`` host spans, (name, start, duration in ns)
    spans: list[tuple[str, int, int]] = dataclasses.field(
        default_factory=list)
    # each device operation's name -> its ``op_name`` path
    scopes: dict[str, str] = dataclasses.field(default_factory=dict)

    @classmethod
    def from_json(cls, path: str) -> "ProgramTrace":
        base = T.Trace.from_json(path)
        with open(path) as f:
            d = json.load(f)
        return cls(base.devices, base.meta, base.host, base.window,
                   spans=[tuple(e) for e in d.get("spans", [])],
                   scopes=d.get("scopes", {}))


def op_name(stats: dict) -> str:
    """An operation's ``op_name`` from the stats of its event metadata:
    ``tf_op`` holds it as ``<op_name>:<op type>``."""
    path = stats.get(OP_NAME_STAT, "")
    return path.rpartition(":")[0] if ":" in path else path


def load(logdir: str, window_span: str = "bench.window") -> ProgramTrace:
    """:func:`harness.trace.load`, with the program's spans and scopes."""
    from jax.profiler import ProfileData

    base = T.load(logdir, window_span)
    path = max(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    op_stats = xspace.event_metadata_strings(path, T.DEVICE_PLANE.match)
    scopes = {}
    for plane, ops in base.devices.items():
        stats = op_stats.get(plane, {})
        for name in {e[0] for e in ops}:
            op = op_name(stats.get(name, {}))
            if op:
                scopes[name] = op
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_SPANS):
                    # without the ``#key=value#`` suffix a keyword of
                    # ``TraceAnnotation`` may leave on the name
                    spans.append((ev.name.split("#", 1)[0],
                                  int(ev.start_ns), int(ev.duration_ns)))
    return ProgramTrace(base.devices, base.meta, base.host, base.window,
                        spans=spans, scopes=scopes)


_TRANSFORM = re.compile(r"^[\w.-]+\((.*)\)$")


def scope_components(path: str) -> set:
    """The names on an ``op_name`` path, with the transforms JAX wraps
    round a scope taken off: ``jit(run)/while/vmap(edge_execute)/add``
    holds ``edge_execute``."""
    out = set()
    for part in path.split("/"):
        m = _TRANSFORM.match(part)
        while m:
            part = m.group(1)
            m = _TRANSFORM.match(part)
        out.add(part)
    return out


def scoped_ns(tr: ProgramTrace, device: str, scope) -> int:
    """Device time of the operations whose ``op_name`` path holds the
    scope component (any of several, given a tuple): the union of their
    intervals in the window."""
    want = {scope} if isinstance(scope, str) else set(scope)
    hit = {n for n in {e[0] for e in tr.devices[device]}
           if want & scope_components(tr.scopes.get(n, ""))}
    lo, hi = tr.window
    return T.union_ns((a, b) for n, a, b in T._clip(tr.devices[device],
                                                     lo, hi)
                      if n in hit)


def _merged(intervals) -> list:
    """Disjoint, sorted ``(start, end)`` intervals with the same union."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap_ns(xs: list, ys: list) -> int:
    """Length of the intersection of two merged interval lists."""
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def window_spans(tr: ProgramTrace, name: str) -> list:
    """The program spans of that name that start inside the window."""
    lo, hi = tr.window
    return [s for s in tr.spans if s[0] == name and lo <= s[1] < hi]


def idle_under_spans_ns(tr: ProgramTrace, device: str,
                        prefix: str = PROGRAM_SPANS) -> int:
    """Idle nanoseconds of the window on the device that lie inside any
    program span whose name starts with ``prefix``."""
    lo, hi = tr.window
    spans = _merged((a, b) for n, a, b in T._clip(tr.spans, lo, hi)
                    if n.startswith(prefix))
    busy = _merged((a, b) for _, a, b in T._clip(tr.devices[device], lo, hi))
    return sum(b - a for a, b in spans) - _overlap_ns(spans, busy)
