"""Whole step: the least bytes a fit must read from HBM (its rounds x one
read of this device's rows of X, ``counts/<name>.py``) over the chip's
peak bandwidth, as a share of the mean fit's wall, in %.  A lower bound
on the work, so it cannot pass 100."""


def read(ctx):
    walls, rounds = ctx["counters"]["fit_walls_s"], ctx["counters"]["rounds"]
    if not walls or not rounds:
        return None
    least_s = rounds * ctx["least"]["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(walls) / len(walls))
