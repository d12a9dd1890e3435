"""Seconds the process spent in backend compiles during set-up, counted
by ``repro.obs.prof.CompileCounter`` (persistent-cache reads included)."""


def read(ctx):
    return ctx["setup_compile_s"]
