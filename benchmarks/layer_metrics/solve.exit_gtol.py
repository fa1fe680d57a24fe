"""Solvers layer: how many of a fit's local L-BFGS solves ended because
the gradient was certified, ``max|g| <= inner_tol``
(``LBFGSState.reason`` == ``lbfgs_core.EXIT_GTOL``,
``solvers/lbfgs_core.py``; a start that already passes the test counts
here too).  ADMM makes one local solve a shard a round, so this and
the three other ``solve.exit_*`` sum to ``solve.rounds`` x the shards.
It is the one exit that says the local problem was solved to the
tolerance asked for; where it reads 0 the float32 loss, not
``inner_tol``, sets how far every solve goes.

A count carried out of the solve in the vector the host fetches for
``n_iter_`` (summed over the shards and the rounds) and put on the
``glm.solve`` span as ``exit_gtol``; mean over the traced fits. Nothing
to read without a trace or where the span or the count is missing (a
parent commit; a solver that counts nothing; a ratio that is no number,
which the span leaves off)."""


def fit_trees(ctx):
    """The span trees of the traced fits: the last ``glm.fit`` roots the
    program recorded, as many as the trace holds ``bench.fit`` spans."""
    if not ctx["trace"]:
        return []
    from dask_ml_tpu import obs

    roots = [r for r in obs.span_records()
             if r.name == "glm.fit" and r.parent_id is None]
    return [obs.span_tree(r) for r in roots[-len(ctx["trace"]["fits"]):]]


def child(tree, name):
    return next((c for c in tree["children"] if c["name"] == name), None)


def read(ctx):
    spans = [child(t, "glm.solve") for t in fit_trees(ctx)]
    values = [(s or {}).get("attrs", {}).get("exit_gtol") for s in spans]
    if not values or None in values:
        return None
    return sum(values) / len(values)
