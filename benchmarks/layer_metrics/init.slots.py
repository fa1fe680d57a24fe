"""k-means|| layer: the distance columns a fit's sampling rounds computed,
the sum over the rounds of the width each round's fold ran at (the
narrowest of the program's static widths that held the round's draw;
``rounds`` x ``cap`` where every fold is ``cap`` wide).

A count carried out of the one program that runs the rounds, fetched with
the candidates and put on the ``kmeans.init`` span as ``slots``; mean over
the traced fits.  Nothing to read without a trace or where the span or
the count is missing (a parent commit; another ``init``)."""


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
    counts = [(s or {}).get("attrs", {}).get("slots") for s in spans]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
