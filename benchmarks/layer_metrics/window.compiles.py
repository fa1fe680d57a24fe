"""Program cache layer: backend compiles (``jax.monitoring``) plus
program-cache misses (``diagnostics.program_report()``) between the
window's start and its end.  Expected 0: every shape is warmed up in
set-up."""


def read(ctx):
    return ctx["counters"]["window_compiles"]
