"""Solvers layer: ``n_iter_`` of the window's last fit (ADMM outer
rounds; Lloyd rounds).  A count."""


def read(ctx):
    return ctx["counters"]["rounds"]
