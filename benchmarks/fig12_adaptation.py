"""Paper Fig. 12: adaptation dynamics — t̂ vs θ(t) overlay, per model.

Runs a θ-shaped mission under an adaptive policy with the flight
recorder (``trace=TraceSpec(t_hat=True)``)
and plots the scheduler's per-tick adapted cloud-latency estimate
t̂_m(t) (``FleetResult.t_hat``, carried out of the tick scan) against
the scenario's θ(t) waveform — one small-multiple panel per model, all
in milliseconds on one shared axis.  The estimator should inflate as the
trapezium rises (sliding-window average clears t̂+ε) and cool back to
the static Table-1 estimate once θ drops and the cooling period expires
(§5.4).

    PYTHONPATH=src python benchmarks/fig12_adaptation.py \
        --out benchmarks/figures/fig12_adaptation.png
    PYTHONPATH=src python benchmarks/fig12_adaptation.py --quick

Requires matplotlib (``pip install matplotlib``); everything else in the
benchmark suite stays matplotlib-free.
"""
from __future__ import annotations

import argparse
import dataclasses
import pathlib

import numpy as np

DEFAULT_OUT = pathlib.Path(__file__).parent / "figures" / \
    "fig12_adaptation.png"

# Validated categorical palette (fixed slot order — identity per model),
# plus ink/surface tokens; see docs/POLICIES.md for the policy being
# traced.  Text wears ink tokens, never the series color.
SERIES = ("#2a78d6", "#eb6834", "#1baf7a", "#eda100", "#e87ba4",
          "#008300", "#4a3aa7", "#e34948")
SURFACE, GRID = "#fcfcfb", "#e8e7e3"
INK, INK_2 = "#0b0b0b", "#52514e"
THETA_FILL, THETA_EDGE = "#dddcd7", "#b5b4ae"


def trace_spec(duration_ms: float):
    """The §8.5 trapezium mission used by the Fig. 11/12 fleet runs,
    ramps scaled into the requested horizon."""
    from repro.scenarios import ScenarioSpec, ThetaTrapezium

    d = duration_ms
    return ScenarioSpec(
        name="fig12-adaptation", duration_ms=d,
        theta=ThetaTrapezium(ramp_up=(0.2 * d, 0.3 * d),
                             ramp_down=(0.7 * d, 0.8 * d)))


def compute(spec, policy: str, seed: int, dt: float = 25.0) -> dict:
    """t̂ trace [T, M] (edge 0), θ trace [T], static t̂ and times [s]."""
    from repro.obs import TraceSpec
    from repro.scenarios import compile_fleet, run_scenario_fleet

    spec = dataclasses.replace(spec, seed=seed)
    res = run_scenario_fleet(spec, policy, dt=dt,
                             trace=TraceSpec(t_hat=True))
    sig = compile_fleet(spec, dt)
    return dict(
        times=np.asarray(sig.times) / 1e3,
        theta=np.asarray(sig.theta)[:, 0],
        t_hat=np.asarray(res.t_hat)[:, 0, :],
        static=np.asarray([m.t_cloud for m in spec.models]),
        names=list(spec.model_names))


def render(data: dict, policy: str, out: pathlib.Path) -> pathlib.Path:
    try:
        import matplotlib
    except ImportError as e:                          # pragma: no cover
        raise SystemExit(
            "fig12_adaptation needs matplotlib (pip install matplotlib); "
            "the rest of the benchmark suite runs without it") from e
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    names, times = data["names"], data["times"]
    n = len(names)
    ncols = 2 if n > 2 else n
    nrows = -(-n // ncols)
    fig, axes = plt.subplots(nrows, ncols, sharex=True, sharey=True,
                             figsize=(4.6 * ncols, 2.4 * nrows),
                             facecolor=SURFACE)
    axes = np.atleast_1d(axes).ravel()
    for ax in axes[n:]:
        ax.set_visible(False)
    for i, (name, ax) in enumerate(zip(names, axes)):
        ax.set_facecolor(SURFACE)
        ax.grid(True, color=GRID, linewidth=0.8)
        ax.set_axisbelow(True)
        for side in ("top", "right"):
            ax.spines[side].set_visible(False)
        for side in ("left", "bottom"):
            ax.spines[side].set_color(GRID)
        ax.tick_params(colors=INK_2, labelsize=8, length=0)
        # θ(t) context: same unit (ms of added WAN latency), neutral fill
        ax.fill_between(times, data["theta"], color=THETA_FILL,
                        edgecolor=THETA_EDGE, linewidth=1.0,
                        label="θ(t) added WAN latency" if i == 0 else None)
        ax.axhline(data["static"][i], color=INK_2, linewidth=1.2,
                   linestyle=(0, (4, 3)),
                   label="static t̂ (Table 1)" if i == 0 else None)
        ax.plot(times, data["t_hat"][:, i], color=SERIES[i % len(SERIES)],
                linewidth=2.0,
                label="adapted t̂ (DEMS-A window)" if i == 0 else None)
        ax.set_title(name, color=INK, fontsize=10, loc="left",
                     fontweight="bold")
    for ax in axes[max(0, n - ncols):n]:
        ax.set_xlabel("mission time [s]", color=INK_2, fontsize=9)
    for ax in axes[0:n:ncols]:
        ax.set_ylabel("latency [ms]", color=INK_2, fontsize=9)
    handles, labels = axes[0].get_legend_handles_labels()
    fig.legend(handles, labels, loc="lower center", ncol=3, frameon=False,
               fontsize=9, labelcolor=INK_2)
    fig.suptitle(f"Fig. 12 — {policy}: adapted cloud-latency estimate "
                 "t̂ vs θ(t), per model", color=INK, fontsize=12, x=0.01,
                 ha="left")
    fig.tight_layout(rect=(0, 0.06, 1, 0.95))
    out.parent.mkdir(parents=True, exist_ok=True)
    fig.savefig(out, dpi=144, facecolor=SURFACE)
    plt.close(fig)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--policy", default="DEMS-A",
                    help="an adaptive fleet policy (DEMS-A, GEMS-A, …)")
    ap.add_argument("--scenario", default=None,
                    help="registry scenario name (default: a trapezium "
                    "mission matching the Fig. 11 fleet runs)")
    ap.add_argument("--duration-ms", type=float, default=300_000.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--quick", action="store_true",
                    help="60 s mission (smoke)")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    args = ap.parse_args()

    duration = 60_000.0 if args.quick else args.duration_ms
    if args.scenario:
        from repro.scenarios import get
        spec = get(args.scenario, duration_ms=duration)
    else:
        spec = trace_spec(duration)
    data = compute(spec, args.policy, args.seed)
    excess = data["t_hat"] - data["static"][None, :]
    out = render(data, args.policy, args.out)
    print(f"wrote {out}")
    print(f"t̂ inflation: peak +{excess.max():.0f} ms; "
          f"{100 * (excess.max(axis=1) > 1.0).mean():.0f}% of mission "
          "above static")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
