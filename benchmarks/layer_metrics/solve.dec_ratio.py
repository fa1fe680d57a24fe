"""Solvers layer: how far the LAST round's local L-BFGS solves stood from
the stall test when they ended: the last iteration's relative decrease
of the local objective over 10 eps (``LBFGSState.rel_dec`` over
``lbfgs_core.stall_threshold``, ``solvers/lbfgs_core.py``), the
largest over the shards.  At or under 1 the float32 loss could no
longer tell two steps apart, which ends a solve; 0 is a step that
passed Armijo and left the loss where it was to the last bit.

A ratio carried out of the solve in the vector the host fetches for
``n_iter_`` (a float32 bit pattern behind the counts) and put on the
``glm.solve`` span as ``dec_ratio``; mean over the traced fits. Nothing
to read without a trace or where the span or the ratio is missing (a
parent commit; a solver that counts nothing; a ratio that is no number,
which the span leaves off)."""


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
    values = [(s or {}).get("attrs", {}).get("dec_ratio") for s in spans]
    if not values or None in values:
        return None
    return sum(values) / len(values)
