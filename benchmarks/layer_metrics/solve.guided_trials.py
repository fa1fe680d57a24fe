"""Solvers layer: those of a fit's line-search trials (``solve.trials``)
that were taken in searches which started from the curvature's guess
(``LBFGSState.n_guided``, ``solvers/lbfgs_core.py``): an L-BFGS
iteration with no history steps along ``-g`` of a loss summed over the
rows, where the step that fits lies some twenty halvings under 1, so
the backtracking search takes ``phi''(0)`` from the cached linear
predictor (one reduction, counted as a trial) and looks first at the
largest power of two under the parabola's Armijo bound.  The count
holds that reduction, the first look, every halving or doubling from
there and, where the walk up reached the unit step, the curvature
test.  Three a search is a guess that stood on the answer; more is how
far it stood from it.  0 under ``probe_grid``, which guesses nothing.

A count carried out of the solve in the vector the host fetches for
``n_iter_`` (over several shards the largest) and put on the
``glm.solve`` span as ``guided_trials``; mean over the traced fits.
Nothing to read without a trace or where the span or the count is
missing (a parent commit; a solver that counts nothing)."""


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
    counts = [(s or {}).get("attrs", {}).get("guided_trials") for s in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
