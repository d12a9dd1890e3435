"""What a tick of the sweep's serial chain costs: the buckets' seconds
from dispatch until their end states are ready, over the ticks they
stepped (each bucket's mission, summed over buckets and passes), from
the ``SweepStats`` record the entry passes, in ms.  A traced run leaves
out the bucket that held the profiler's stop."""


def read(ctx):
    layer = ctx["layer"]
    if not layer.get("sweep_clean_bucket_ticks"):
        return None
    return 1e3 * layer["sweep_clean_bucket_s"] / \
        layer["sweep_clean_bucket_ticks"]
