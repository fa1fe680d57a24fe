"""Lloyd layer: the fused Lloyd loop's share of the HBM roofline, in %.
Per traced fit: the iterations the program counted (``iters`` on
``kmeans.lloyd``) x one read of this device's rows of X
(``counts/<name>.py``) over the chip's peak bandwidth, as a share of the
device seconds of the configuration's ``lloyd_modules`` inside that fit's
``bench.fit`` span; mean over the fits.  Every iteration reads the rows at
least once, so the bytes are a lower bound and the share cannot pass 100.
Nothing to read without a trace, without the count, or where no Lloyd
module ran."""


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
    trees = fit_trees(ctx)
    names = ctx["cell"]["config_data"].get("lloyd_modules", [])
    shares = []
    for tree, fit in zip(trees, ctx["trace"]["fits"] if trees else []):
        iters = (child(tree, "kmeans.lloyd") or {}).get("attrs", {}).get(
            "iters")
        device_s = sum(fit["modules"].get(n, 0.0) for n in names)
        if iters is None or not device_s:
            return None
        least_s = iters * ctx["least"]["bytes"] / ctx["peaks"][
            "hbm_bytes_per_s"]
        shares.append(100.0 * least_s / device_s)
    return sum(shares) / len(shares) if shares else None
