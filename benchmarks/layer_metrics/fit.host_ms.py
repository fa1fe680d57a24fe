"""Estimator API layer: per traced fit, the wall of its ``bench.fit``
span less the union of the device-busy intervals inside it, in ms.  What
the host spends where the chip waits: dispatch, Python, fetches."""


def read(ctx):
    fits = ctx["trace"]["fits"] if ctx["trace"] else []
    if not fits:
        return None
    return 1e3 * sum(f["wall_s"] - f["busy_s"] for f in fits) / len(fits)
