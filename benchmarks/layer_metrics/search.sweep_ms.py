"""Packed lanes layer: a fold's candidates fitted as the lanes of ONE
vmapped whole-solve program (``solvers/algorithms.py :: lambda_sweep``,
program ``jit__sweep_lanes``), from its dispatch to the lanes'
coefficients and iteration counts on the host (one ``device_get``).

Read from the program's own spans: the durations of the ``search.sweep``
spans of each traced fit's ``search.fit`` tree, summed over the fit's
folds, mean over those fits, in ms.  Nothing to read without a trace or
where the program opens no such span (a parent commit; a search that did
not pack)."""


def fit_trees(ctx):
    """The span trees of the traced fits: the last ``search.fit`` roots
    the program recorded, as many as the trace holds ``bench.fit`` spans."""
    if not ctx["trace"]:
        return []
    from dask_ml_tpu import obs

    roots = [r for r in obs.span_records()
             if r.name == "search.fit" and r.parent_id is None]
    return [obs.span_tree(r) for r in roots[-len(ctx["trace"]["fits"]):]]


def children(tree, name):
    return [c for c in tree["children"] if c["name"] == name]


def read(ctx):
    per_fit = [[s["dur_s"] for s in children(t, "search.sweep")]
               for t in fit_trees(ctx)]
    if not per_fit or not all(per_fit):
        return None
    return 1e3 * sum(map(sum, per_fit)) / len(per_fit)
