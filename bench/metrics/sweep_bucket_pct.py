"""Share of the window spent running the sweep's buckets: each
``run_batch`` call of ``run_registry_sweep``, from its dispatch until its
end state is ready on the device, read on the host clock, in %."""


def read(ctx):
    layer = ctx["layer"]
    if "sweep_bucket_s" not in layer or not layer.get("window_s"):
        return None
    return 100.0 * layer["sweep_bucket_s"] / layer["window_s"]
