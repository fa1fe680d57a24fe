"""Whole step: the least FLOPs a fit needs (its rounds x one round's,
``counts/<name>.py``, on one device) over the chip's peak FLOP/s, as a
share of the mean fit's wall, in %.  Every device does its own rows'
work in the same wall, so the share per chip is the share of them all."""


def read(ctx):
    walls, rounds = ctx["counters"]["fit_walls_s"], ctx["counters"]["rounds"]
    if not walls or not rounds:
        return None
    least_s = rounds * ctx["least"]["flops"] / ctx["peaks"]["flops_per_s"]
    return 100.0 * least_s / (sum(walls) / len(walls))
