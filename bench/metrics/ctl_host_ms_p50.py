"""Median host time of a ``poll`` that stepped the controller: the
benchmark's wall time around the call minus the controller's own step
span for the windows it stepped, in ms."""
import statistics


def read(ctx):
    host = ctx["layer"].get("ctl_host_ms")
    return statistics.median(host) if host else None
