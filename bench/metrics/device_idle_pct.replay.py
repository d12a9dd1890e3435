"""Idle share of the traced window on the busiest device, in percent:
1 - (union of its operations' intervals) / window."""
from harness import trace as T


def read(ctx):
    tr = ctx["trace"]
    dev = T.busiest(tr) if tr is not None else None
    if dev is None:
        return None
    lo, hi = tr.window
    return 100.0 * (1.0 - T.busy_ns(tr, dev) / (hi - lo))
