"""TSQR factor layer: the reads of the table a fit's factorization made
(the mean, the Gram, the repair: 3; one more where the Householder arm
was dispatched after the guard's verdict).

A count assembled inside the one program that makes the passes, fetched
with the guard's verdict in one ``int32[2]`` and put on the ``pca.factor``
span as ``passes``; mean over the traced fits.  Nothing to read without a
trace or where the span or the count is missing (a parent commit)."""


def fit_trees(ctx):
    """The span trees of the traced fits: the last ``pca.fit`` roots the
    program recorded, as many as the trace holds ``bench.fit`` spans."""
    if not ctx["trace"]:
        return []
    from dask_ml_tpu import obs

    roots = [r for r in obs.span_records()
             if r.name == "pca.fit" and r.parent_id is None]
    return [obs.span_tree(r) for r in roots[-len(ctx["trace"]["fits"]):]]


def child(tree, name):
    return next((c for c in tree["children"] if c["name"] == name), None)


def read(ctx):
    spans = [child(t, "pca.factor") for t in fit_trees(ctx)]
    counts = [(s or {}).get("attrs", {}).get("passes") for s in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
