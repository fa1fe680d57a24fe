"""Solvers layer: trial steps of the line searches in a fit's local
solves whose value came from the cached linear predictor
(``LBFGSState.n_trials``, ``solvers/lbfgs_core.py``), a batched grid of
candidates counting once: work on vectors of a row's length that does
not read this device's rows of X (those reads are ``solve.passes``).

A count carried out of the solve in the vector the host fetches for
``n_iter_`` and put on the ``glm.solve`` span as ``trials``; mean over
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
    counts = [(s or {}).get("attrs", {}).get("trials") for s in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
