"""TSQR factor layer: from the dispatch of the factorization (the mean, the
Gram and the CholeskyQR2 repair of ``linalg/tsqr.py :: factor_r``, one
program) to the host's reading of its verdict; the small spectrum program
is queued behind it inside this span, so the span's end is the factor's.

Read from the program's own spans (``dask_ml_tpu/obs/spans.py``, live
while the profiler session of a ``--trace 1`` run is on): the duration of
``pca.factor`` in each traced fit's ``pca.fit`` tree, mean over those
fits, in ms.  Nothing to read without a trace or where the program opens
no such span (a parent commit that has none)."""


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
    if not spans or None in spans:
        return None
    return 1e3 * sum(s["dur_s"] for s in spans) / len(spans)
