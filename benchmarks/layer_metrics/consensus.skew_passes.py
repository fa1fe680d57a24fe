"""ADMM consensus: the reads of X the fastest shard sat out.  Each round
ends in an all-reduce that waits for the slowest shard's local solve;
``skew_passes`` is, summed over a fit's rounds, that shard's
``LBFGSState.n_evals`` less the fastest shard's (both come out of the
solve in the one all-reduce that carried the slowest's alone before).

A count on the ``glm.solve`` span, fetched in the vector ``n_iter_``
comes in; mean over the traced fits.  0 on one shard.  Nothing to read
without a trace or where the span or the count is missing (a parent
commit; a solver without a consensus)."""


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
    counts = [(s or {}).get("attrs", {}).get("skew_passes") for s in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
