"""Refit layer: the chosen candidate fitted on all rows
(``_BaseSearchCV._fit``'s last step; the winner's own ``glm.fit`` tree
hangs under the span).

Read from the program's own spans: the duration of the ``search.refit``
span of each traced fit's ``search.fit`` tree, mean over those fits, in
ms.  Nothing to read without a trace or where the program opens no such
span (a parent commit; ``refit=False``)."""


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
    per_fit = [[s["dur_s"] for s in children(t, "search.refit")]
               for t in fit_trees(ctx)]
    if not per_fit or not all(per_fit):
        return None
    return 1e3 * sum(map(sum, per_fit)) / len(per_fit)
