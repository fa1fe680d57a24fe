"""Solvers layer: how far the LAST round's local L-BFGS solves stood from
the gradient test when they ended: ``max|g|`` at the solve's last
point over ``inner_tol`` (``LBFGSState.g_max``,
``solvers/lbfgs_core.py``), the largest over the shards.  Under 1 the
test was met; in the thousands and more the float32 gradient of a loss
summed over the rows cannot be certified at that tolerance, and
another test ended the solve (``solve.exit_*`` says which).

A ratio carried out of the solve in the vector the host fetches for
``n_iter_`` (a float32 bit pattern behind the counts) and put on the
``glm.solve`` span as ``grad_ratio``; mean over the traced fits. Nothing
to read without a trace or where the span or the ratio is missing (a
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
    values = [(s or {}).get("attrs", {}).get("grad_ratio") for s in spans]
    if not values or None in values:
        return None
    return sum(values) / len(values)
