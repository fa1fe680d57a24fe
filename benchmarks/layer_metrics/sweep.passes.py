"""Packed lanes layer: the reads of a fold's train rows that the slowest
lane's solve made (``LBFGSState.n_evals``: calls of the black-box
objective or of its ``value_and_grad``), summed over a fit's folds.  The
lanes search in lock-step, so a turn of the program costs what its
slowest lane takes and this is a least count of the program's own reads;
``sweep.hbm_roof_pct`` counts ONE read an iteration, and this says how
many there were.

A count the lanes' program returns (``lambda_sweep(return_counts=True)``,
fetched with the coefficients) and ``_search.py :: _publish_lanes`` puts
on ``search.sweep`` as ``passes_max``; summed over a fit's folds, mean
over the traced fits.  Nothing to read without a trace or where the span
or the count is missing (a parent commit; a runner that counts no
passes)."""


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
        values = [s["attrs"].get("passes_max")
                  for s in children(tree, "search.sweep")]
        if not values or None in values:
            return None
        per_fit.append(sum(values))
    return sum(per_fit) / len(per_fit) if per_fit else None
