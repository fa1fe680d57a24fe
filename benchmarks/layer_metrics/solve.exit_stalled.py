"""Solvers layer: how many of a fit's local L-BFGS solves ended because
the float32 loss stopped falling: the last iteration's relative
decrease was at or under 10 eps (``LBFGSState.reason`` ==
``lbfgs_core.EXIT_STALLED``, ``solvers/lbfgs_core.py``) while
``max|g|`` still stood over ``inner_tol``.  ADMM makes one local solve
a shard a round, so this and the three other ``solve.exit_*`` sum to
``solve.rounds`` x the shards.  Such a stop moves with the last bit of
a loss summed over the rows: a change that rounds the path otherwise
can move it.

A count carried out of the solve in the vector the host fetches for
``n_iter_`` (summed over the shards and the rounds) and put on the
``glm.solve`` span as ``exit_stalled``; mean over the traced fits.
Nothing to read without a trace or where the span or the count is
missing (a parent commit; a solver that counts nothing; a ratio that is
no number, which the span leaves off)."""


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
    values = [(s or {}).get("attrs", {}).get("exit_stalled") for s in spans]
    if not values or None in values:
        return None
    return sum(values) / len(values)
