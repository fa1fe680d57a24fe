"""Solvers layer: calls of the objective or of its ``value_and_grad`` in a
fit's local solves, a batched line-search grid counting once
(``LBFGSState.n_evals``, ``solvers/lbfgs_core.py``): a lower bound on the
reads of this device's rows of X.

A count carried out of the solve in the vector the host fetches for
``n_iter_`` and put on the ``glm.solve`` span as ``passes``; mean over
the traced fits.  Nothing to read without a trace or where the span or
the count is missing (a parent commit; a solver that counts nothing)."""


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
    counts = [(s or {}).get("attrs", {}).get("passes") for s in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
