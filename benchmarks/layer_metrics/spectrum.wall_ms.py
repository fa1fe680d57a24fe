"""Small SVD layer: what is left of a fit once the factor's verdict is
read: the (d, d) SVD of ``R``, the sign flip and the fitted statistics
(one program, queued behind the factorization), the start of the fitted
arrays' copies to the host and the wait for them.

Read from the program's own spans: the duration of ``pca.spectrum`` in
each traced fit's ``pca.fit`` tree, mean over those fits, in ms.  Nothing
to read without a trace or where the program opens no such span."""


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
    spans = [child(t, "pca.spectrum") for t in fit_trees(ctx)]
    if not spans or None in spans:
        return None
    return 1e3 * sum(s["dur_s"] for s in spans) / len(spans)
