"""The selection's share of its memory roofline, in percent.

The least time the selections of the traced window could take is the
bytes they need over the chip's HBM bandwidth (``bench/peaks.json``);
their time is the device time of the operations matched by name and
signature, as in ``argext_busy_pct``.  A selection does no arithmetic
worth counting, so the bytes bound it.  Bytes come from the unpadded call shapes: scores
(float32) and mask (bool) read, index (int32) and value (float32)
written per row.
"""
from harness import trace as T

PATTERNS = [r"_argext_kernel", r"masked_argext",
            r"^%\S+ = \(s32\[(?:\d+,)*1\]\{[^}]*\}, f32\[(?:\d+,)*1\]"
            r'\{[^}]*\}\) custom-call\(.*custom_call_target="tpu_custom_call"']


def call_bytes(rows: int, n: int) -> int:
    """Bytes one selection over ``rows`` rows of ``n`` candidates needs."""
    return rows * (n * (4 + 1) + 4 + 4)


def bytes_per_tick(layer: dict) -> int:
    """The tick's selections: one steal selection per executor substep
    over every edge's cloud queue; with cooperation, per exchange round,
    three selections over the edges (least-loaded, source, destination)
    and one export selection over the source's edge queue."""
    e = layer["n_edges"]
    total = layer["substeps"] * call_bytes(e, layer["cloud_cap"])
    if layer["coop"]:
        total += layer["coop_rounds"] * (3 * call_bytes(1, e)
                                         + call_bytes(1, layer["edge_cap"]))
    return total


def read(ctx):
    tr, layer = ctx["trace"], ctx["layer"]
    dev = T.busiest(tr) if tr is not None else None
    if dev is None or not layer.get("ticks"):
        return None
    sel_ns = T.matched_ns(tr, dev, PATTERNS)
    if sel_ns == 0:
        return None
    least_s = bytes_per_tick(layer) * layer["ticks"] \
        / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (sel_ns / 1e9)
