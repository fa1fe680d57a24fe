"""TSQR factor layer: the factorization's share of the chip's roofline, in
%.  Per traced fit: the sum over the passes of the least time of each on
this device (``counts/<name>.py :: factor_passes``: the larger of one read
of its rows of X over the peak bandwidth and the pass's FLOPs over the
peak FLOP/s), as a share of the device seconds of the configuration's
``factor_modules`` inside that fit's ``bench.fit`` span; mean over the
fits.  The passes counted are the three the exact route cannot do
without; a fit that made more (the fallback) spent more device time on
the same least work.  A lower bound on the work, so the share cannot pass
100.  Nothing to read without a trace, where the program left no
``pca.factor`` span with its count, or where no factor module ran."""


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
    trees = fit_trees(ctx)
    names = ctx["cell"]["config_data"].get("factor_modules", [])
    least, peaks = ctx["least"], ctx["peaks"]
    if "factor_passes" not in least:
        return None
    least_s = sum(max(b / peaks["hbm_bytes_per_s"], f / peaks["flops_per_s"])
                  for b, f in least["factor_passes"])
    shares = []
    for tree, fit in zip(trees, ctx["trace"]["fits"] if trees else []):
        passes = (child(tree, "pca.factor") or {}).get("attrs", {}).get(
            "passes")
        device_s = sum(fit["modules"].get(n, 0.0) for n in names)
        if passes is None or not device_s:
            return None
        shares.append(100.0 * least_s / device_s)
    return sum(shares) / len(shares) if shares else None
