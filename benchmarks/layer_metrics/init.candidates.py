"""k-means|| layer: the candidates a fit's init sampled, the first one
included (the valid slots of the candidate buffer: what the host's
weighted k-means++ is given).

A count put on the ``kmeans.init`` span as ``candidates``; mean over the
traced fits.  Nothing to read without a trace or where the span or the
count is missing (a parent commit; another ``init``)."""


def fit_trees(ctx):
    """The span trees of the traced fits: the last ``kmeans.fit`` roots
    the program recorded, as many as the trace holds ``bench.fit`` spans."""
    if not ctx["trace"]:
        return []
    from dask_ml_tpu import obs

    roots = [r for r in obs.span_records()
             if r.name == "kmeans.fit" and r.parent_id is None]
    return [obs.span_tree(r) for r in roots[-len(ctx["trace"]["fits"]):]]


def child(tree, name):
    return next((c for c in tree["children"] if c["name"] == name), None)


def read(ctx):
    spans = [child(t, "kmeans.init") for t in fit_trees(ctx)]
    counts = [(s or {}).get("attrs", {}).get("candidates") for s in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
