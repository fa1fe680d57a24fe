"""TSQR factor layer: the fits of the traced window whose CholeskyQR2
guard did not hold, so that the Householder arm was dispatched in its
place (``fallback`` 0 / 1 on the ``pca.factor`` span, from the verdict the
program fetches with its count of passes); summed over the traced fits.
0 on a table inside CholeskyQR2's regime.  Nothing to read without a
trace or where the span or the verdict is missing (a parent commit)."""


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
    flags = [(s or {}).get("attrs", {}).get("fallback") for s in spans]
    if not flags or None in flags:
        return None
    return sum(flags)
