"""Packed lanes layer: the trials of the lanes' black-box line searches,
each a read of the fold's train rows: the most any lane's searches took,
summed over a fit's folds.  A lane's passes (``sweep.passes``) less one at
its start and one an iteration (the gradient at the accepted step, which
``backtrack`` evaluates again): what a search on a cached linear
predictor would take off the table (ROADMAP R8).

A count ``_search.py :: _publish_lanes`` puts on ``search.sweep`` as
``trials_max`` from the lanes' own counts
(``lambda_sweep(return_counts=True)``, fetched with the coefficients);
summed over a fit's folds, mean over the traced fits.  Nothing to read
without a trace or where the span or the count is missing (a parent
commit; a runner that counts no passes)."""


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
    per_fit = []
    for tree in fit_trees(ctx):
        values = [s["attrs"].get("trials_max")
                  for s in children(tree, "search.sweep")]
        if not values or None in values:
            return None
        per_fit.append(sum(values))
    return sum(per_fit) / len(per_fit) if per_fit else None
