"""Share of the window spent in the sweep's lowering
(``compile_registry_groups``, called by ``run_registry_sweep`` once a
pass), read on the host clock round each call, in %."""


def read(ctx):
    layer = ctx["layer"]
    if "sweep_lower_s" not in layer or not layer.get("window_s"):
        return None
    return 100.0 * layer["sweep_lower_s"] / layer["window_s"]
