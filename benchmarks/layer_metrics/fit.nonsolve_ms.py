"""Estimator API layer: per traced fit, the device time of every XLA
module inside its ``bench.fit`` span that is not one of the solver's
(``solve_modules`` in the configuration), in ms: class discovery, the
intercept column, masks, tolerances, the final assignment."""


def read(ctx):
    names = set(ctx["cell"]["config_data"].get("solve_modules", []))
    fits = ctx["trace"]["fits"] if ctx["trace"] else []
    if not fits:
        return None
    per_fit = [sum(s for n, s in f["modules"].items() if n not in names)
               for f in fits]
    return 1e3 * sum(per_fit) / len(per_fit)
