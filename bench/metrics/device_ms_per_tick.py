"""Device busy time of the busiest device per tick stepped in the traced
window, in ms."""
from harness import trace as T


def read(ctx):
    tr, ticks = ctx["trace"], ctx["layer"].get("ticks")
    dev = T.busiest(tr) if tr is not None else None
    if dev is None or not ticks:
        return None
    return T.busy_ns(tr, dev) / 1e6 / ticks
