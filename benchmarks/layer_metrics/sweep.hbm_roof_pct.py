"""Packed lanes layer: the sweeps' share of the HBM roofline, in %.  Per
traced fit: for each fold the slowest lane's iterations (``iters_max`` on
``search.sweep``) x one read of the fold's train rows of X
(``train_bytes`` of ``counts/<name>.py``) over the chip's peak bandwidth,
summed over the folds, as a share of the device seconds of the
configuration's ``sweep_modules`` inside that fit's ``bench.fit`` span;
mean over the fits.  Every iteration of the slowest lane reads the train
rows at least once, and the lanes share that read, so the bytes are a
lower bound and the share cannot pass 100 (the black-box line search
reads them again for every trial: that is what the share leaves out).
Nothing to read without a trace, without the counts, or where no sweep
module ran."""


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
    trees = fit_trees(ctx)
    names = ctx["cell"]["config_data"].get("sweep_modules", [])
    train_bytes = ctx["least"].get("train_bytes")
    shares = []
    for tree, fit in zip(trees, ctx["trace"]["fits"] if trees else []):
        iters = [s["attrs"].get("iters_max")
                 for s in children(tree, "search.sweep")]
        device_s = sum(fit["modules"].get(n, 0.0) for n in names)
        if not iters or None in iters or not train_bytes or not device_s:
            return None
        least_s = sum(iters) * train_bytes / ctx["peaks"]["hbm_bytes_per_s"]
        shares.append(100.0 * least_s / device_s)
    return sum(shares) / len(shares) if shares else None
