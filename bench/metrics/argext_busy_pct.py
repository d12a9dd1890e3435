"""Share of the busiest device's busy time spent in the selection
(``kernels/sched_ops.masked_argext``), in percent.

The selection is found by name and signature.  On the chip the Mosaic
kernel is a ``custom-call`` with target ``tpu_custom_call`` and no kernel
name (``pallas_call`` is given none), so the pattern also asks for the
selection's own result: a tuple of an ``s32`` index and an ``f32`` value,
each with a last dimension of 1.  Another Pallas kernel, with other
results, is not counted here; it needs a metric file of its own.  The
kernel's name and the ``named_scope`` a later change may put round
``masked_argext`` are listed too, so that a named kernel, or the jnp path
the selection takes elsewhere, is read the same way.
"""
from harness import trace as T

PATTERNS = [r"_argext_kernel", r"masked_argext",
            r"^%\S+ = \(s32\[(?:\d+,)*1\]\{[^}]*\}, f32\[(?:\d+,)*1\]"
            r'\{[^}]*\}\) custom-call\(.*custom_call_target="tpu_custom_call"']


def read(ctx):
    tr = ctx["trace"]
    dev = T.busiest(tr) if tr is not None else None
    if dev is None:
        return None
    sel = T.matched_ns(tr, dev, PATTERNS)
    busy = T.busy_ns(tr, dev)
    if sel == 0 or busy == 0:
        return None
    return 100.0 * sel / busy
