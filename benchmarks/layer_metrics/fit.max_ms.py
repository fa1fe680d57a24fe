"""Estimator API layer: the slowest whole fit of the window on the host
clock, in ms.  Shows a stall; decides no PR (a window holds too few fits
for a percentile)."""


def read(ctx):
    walls = ctx["counters"]["fit_walls_s"]
    return 1e3 * max(walls) if walls else None
