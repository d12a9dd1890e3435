"""Share of the window spent gathering the sweep's end states to the
host: each bucket's ``jax.device_get`` in ``run_registry_sweep`` (its
``fleet.sweep.gather`` span), from the ``SweepStats`` record the entry
passes, on the host clock, in %."""


def read(ctx):
    layer = ctx["layer"]
    if "sweep_gather_s" not in layer or not layer.get("window_s"):
        return None
    return 100.0 * layer["sweep_gather_s"] / layer["window_s"]
