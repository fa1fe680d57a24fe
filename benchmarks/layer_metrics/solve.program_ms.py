"""Solvers layer: per traced fit, the device time of the solver's own XLA
modules inside its ``bench.fit`` span (the trace's module line), in ms.
The configuration names them (``solve_modules``: the whole-solve ADMM
program; the k-means|| rounds and the fused Lloyd loop).  If none of them
ran, there is nothing to read."""


def read(ctx):
    names = ctx["cell"]["config_data"].get("solve_modules", [])
    fits = ctx["trace"]["fits"] if ctx["trace"] else []
    per_fit = [sum(f["modules"].get(n, 0.0) for n in names) for f in fits]
    if not per_fit or not any(per_fit):
        return None
    return 1e3 * sum(per_fit) / len(per_fit)
