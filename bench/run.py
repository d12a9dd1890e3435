#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload metro1024-steady-demsa --seed 7 \\
        --seconds 20 --trace 0

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic file under
``bench/traffic/`` (whose ``entry`` picks ``bench/harness/<entry>.py``:
``replay``, ``live`` or ``sweep``), and one reader per per-layer metric
under ``bench/metrics/``.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace of the
same window.  Every line but the last is information; the numbers
compared for ``correct`` are the last lines on standard error and the
last key of the result.  The run exits non-zero, with no result line,
when JAX finds no TPU, fewer chips than the cell asks for, or a device
that ``bench/peaks.json`` does not list.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time


def _process_t0() -> float:
    """``time.perf_counter()`` at the moment this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import check, gen, program_trace  # noqa: E402
from harness import trace as trace_lib  # noqa: E402


class NoChip(Exception):
    """The machine cannot run this cell; no result is printed."""


def load_spec(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(spec: dict, workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell, its configuration, traffic and per-layer metric readers,
    each found by its name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(root / conf["file"]) as f:
        cfg = json.load(f)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    reported = {m["name"] for m in e2e}
    layers = [m for m in spec["per_layer"]
              if cell["name"] in m.get("workloads", [cell["name"]])
              and m["moves"] in reported]
    readers = {m["name"]: root / "bench" / "metrics" / f"{m['name']}.py"
               for m in layers}
    return dict(cell=cell, cfg=cfg, traffic=traffic, e2e=e2e,
                layers=layers, readers=readers)


def load_reader(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(chips: int, peaks: dict) -> dict:
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    if d0.device_kind not in peaks:
        raise NoChip(f"device kind {d0.device_kind!r} is not in "
                     f"bench/peaks.json")
    return dict(platform=d0.platform, kind=d0.device_kind, count=chips)


class Window:
    """The measured span: host clock, profiler and compile count."""

    def __init__(self, cell: "Cell"):
        self.cell, self.t0, self.seconds = cell, None, None

    def span(self, name: str):
        if self.cell.trace:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


class Cell:
    """One run of one cell: what the entries read and record."""

    def __init__(self, resolved: dict, seed: int, seconds: float,
                 trace: bool, say=print, proc_t0: float | None = None):
        self.__dict__.update(resolved)
        self.proc_t0 = time.perf_counter() if proc_t0 is None else proc_t0
        self.name = self.cell["name"]
        self.chips = int(self.cell["chips"])
        self.seed, self.seconds, self.trace = int(seed), seconds, trace
        self.metrics: dict = {}
        self.layer: dict = {}
        self.checks: dict = {}
        self.memory_peak = None
        self.setup_compile_s = None
        self.trace_dir = None
        self._traced = None
        self._say = say

    # -- what entries call -------------------------------------------------
    def say(self, msg: str) -> None:
        self._say(msg)

    def mark(self, what: str) -> None:
        """Say how far into the process a step of set-up ended."""
        self.say(f"set-up: {what} at "
                 f"{time.perf_counter() - self.proc_t0:.3f} s")

    def key(self):
        return gen.seed_key(self.seed)

    def require_program_matches(self, fj) -> None:
        """The program's fixed sizes must be the configuration's."""
        s = self.cfg["scheduler"]
        have = dict(edge_queue_cap=fj.EDGE_CAP, cloud_queue_cap=fj.CLOUD_CAP,
                    substeps=fj.SUBSTEPS)
        bad = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
        if bad:
            raise ValueError(f"program sizes differ from the configuration "
                             f"(program, configuration): {bad}")

    @contextlib.contextmanager
    def setup_span(self):
        from repro.obs.prof import CompileCounter
        with CompileCounter() as cc:
            yield
        self.setup_compile_s = cc.total_secs
        self.say(f"set-up: {cc.count} backend compiles, "
                 f"{cc.total_secs:.6f} s compiling")

    @contextlib.contextmanager
    def window(self):
        from repro.obs.prof import CompileCounter
        w = Window(self)
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            # no reader reads the Python tracer's function events, and
            # they slow the host code of the window
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._traced = w.span("bench.window")
            self._traced.__enter__()
        try:
            with CompileCounter() as cc:
                w.t0 = time.perf_counter()
                self.metric("setup_s", w.t0 - self.proc_t0, "s")
                yield w
                w.seconds = time.perf_counter() - w.t0
        finally:
            self.stop_trace()
        self.layer["window_s"] = w.seconds
        self.say(f"window: {w.seconds:.6f} s, {cc.count} backend compiles "
                 f"inside it ({cc.total_secs:.6f} s)")

    def stop_trace(self) -> None:
        """End the traced part of the window (its ``bench.window`` span)
        and stop the profiler: at the window's end, or sooner where an
        entry's window holds more device events than a profile can keep.
        The measured window goes on; the trace's window is what was
        traced."""
        if self._traced is None:
            return
        self._traced.__exit__(None, None, None)
        self._traced = None
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.say(f"trace: profiler stopped in "
                 f"{time.perf_counter() - t0:.3f} s")

    def metric(self, name: str, value: float, unit: str) -> None:
        self.metrics[name] = {"value": float(value), "unit": unit}

    def read_memory(self, devices) -> None:
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in devices if d.memory_stats()]
        self.memory_peak = int(max(peaks)) if peaks else None

    def free_device(self) -> None:
        gc.collect()

    def limit(self, name: str):
        return self.cfg["limits"].get(name)

    def record(self, name: str, value) -> None:
        self.checks[name] = {"value": value, "limit": self.limit(name)}

    def compare_replay(self, got: dict, segments: list,
                       dtype=np.float32) -> None:
        t0 = time.perf_counter()
        want, arrived = check.ref_replay(self.cfg, self.traffic["policy"],
                                         segments, dtype)
        self.say(f"reference: {len(arrived)} edges over "
                 f"{sum(s['times'].shape[0] for s in segments)} ticks in "
                 f"{time.perf_counter() - t0:.3f} s")
        self.record("ledger_gap", check.ledger_gap(got, arrived))
        self.record("mismatch_pct", check.mismatch_pct(got, want, arrived))

    # -- result --------------------------------------------------------------
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["limit"] is not None and c["value"] <= c["limit"]
            for c in self.checks.values())


def per_layer(cell: Cell, dev: dict, tr) -> dict:
    """Read each of the cell's per-layer metrics; a reader that finds
    nothing to read returns None and its metric is left out."""
    peaks = json.loads((BENCH / "peaks.json").read_text())
    ctx = dict(trace=tr, layer=cell.layer, peak=peaks[dev["kind"]],
               setup_compile_s=cell.setup_compile_s, chips=cell.chips)
    out = {}
    for m in cell.layers:
        value = load_reader(cell.readers[m["name"]]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, dev: dict) -> dict:
    entry = cell.traffic["entry"]
    mod = importlib.import_module(f"harness.{entry}")
    status = mod.run(cell)
    result = dict(correct=cell.correct(), attempted=status["attempted"],
                  failed=status["failed"])
    device = dict(dev, memory_peak_bytes=cell.memory_peak)
    if cell.trace:
        t0 = time.perf_counter()
        tr = program_trace.load(cell.trace_dir)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        cell.say(f"trace: {sum(len(v) for v in tr.devices.values())} device "
                 f"operations read in {time.perf_counter() - t0:.3f} s")
        chips = sorted(tr.devices)
        busy = [trace_lib.busy_ns(tr, d) / 1e9 for d in chips]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = (tr.window[1] - tr.window[0]) / 1e9
        result["metrics"] = per_layer(cell, dev, tr)
        top = trace_lib.busiest(tr)
        if top is not None:
            result["breakdown"] = dict(
                device_ops=trace_lib.top_ops(tr, top),
                idle_gaps=trace_lib.idle_gaps(tr, top))
    else:
        result["metrics"] = {m["name"]: cell.metrics[m["name"]]
                             for m in cell.e2e}
    result["device"] = device
    result["checks"] = cell.checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    proc_t0 = _process_t0()
    peaks = json.loads((BENCH / "peaks.json").read_text())
    resolved = resolve(load_spec(), args.workload)
    try:
        dev = device_info(int(resolved["cell"]["chips"]), peaks)
    except NoChip as e:
        print(f"bench: {e}; nothing ran", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # one fixed cache inside the checkout unless the caller names another
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"device: {dev} jax={jax.__version__} cache={cache}; at "
          f"{time.perf_counter() - proc_t0:.3f} s", flush=True)
    cell = Cell(resolved, args.seed, args.seconds, bool(args.trace),
                say=lambda m: print(m, flush=True), proc_t0=proc_t0)
    result = run_cell(cell, dev)
    for name, c in cell.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
