"""Estimator API layer: what ``glm.fit`` spends outside its children
(slicing ``coef_``, ``float(intercept_)``, Python): the root span's
duration less the UNION of its child spans' intervals (not their sum: a
child that overlapped another would be taken out twice), mean over the
traced fits, in ms.  Read from the program's own spans; nothing to read
without a trace or where the program opens no ``glm.fit`` span."""


def fit_trees(ctx):
    """The span trees of the traced fits: the last ``glm.fit`` roots the
    program recorded, as many as the trace holds ``bench.fit`` spans."""
    if not ctx["trace"]:
        return []
    from dask_ml_tpu import obs

    roots = [r for r in obs.span_records()
             if r.name == "glm.fit" and r.parent_id is None]
    return [obs.span_tree(r) for r in roots[-len(ctx["trace"]["fits"]):]]


def self_seconds(tree):
    covered, at = 0.0, tree["t0"]
    for c in sorted(tree["children"], key=lambda c: c["t0"]):
        lo, hi = max(c["t0"], at), min(c["t1"], tree["t1"])
        if hi > lo:
            covered, at = covered + hi - lo, hi
    return tree["dur_s"] - covered


def read(ctx):
    trees = fit_trees(ctx)
    if not trees:
        return None
    return 1e3 * sum(self_seconds(t) for t in trees) / len(trees)
