"""Median of the controller's own step span
(``FleetController.step_latencies_ms``: wall time round ``step_chunk``
and ``block_until_ready``) over the window, in ms."""
import statistics


def read(ctx):
    steps = ctx["layer"].get("ctl_step_ms")
    return statistics.median(steps) if steps else None
