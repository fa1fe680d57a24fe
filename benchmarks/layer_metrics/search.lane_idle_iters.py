"""Packed lanes layer: the iterations a sweep's finished lanes sat out
while its slowest lane ran.  The lanes of one vmapped ``while_loop`` turn
until the last is done, and a finished lane's turn still reads X; so this
is the packing's waste, in lane-iterations: the sum over the lanes of
``iters_max - iters``.

A count the lanes' program returns (``lambda_sweep``'s per-lane
iterations, fetched with the coefficients) and ``_search.py ::
_publish_lanes`` puts on ``search.sweep`` as ``lane_idle_iters``; summed
over a fit's folds, mean over the traced fits.  Nothing to read without a
trace or where the span or the count is missing (a parent commit)."""


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
        values = [s["attrs"].get("lane_idle_iters")
                  for s in children(tree, "search.sweep")]
        if not values or None in values:
            return None
        per_fit.append(sum(values))
    return sum(per_fit) / len(per_fit) if per_fit else None
