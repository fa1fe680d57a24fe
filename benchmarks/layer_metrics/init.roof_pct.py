"""k-means|| layer: the init's share of the chip's roofline, in %.  Per
traced fit: the rounds the program counted (``rounds`` on ``kmeans.init``)
x the least time of one round on this device (``counts/<name>.py``: the
larger of one read of its rows of X over the peak bandwidth and the
products with the round's ``cap`` candidates over the peak FLOP/s), as a
share of the device seconds of the configuration's ``init_modules`` inside
that fit's ``bench.fit`` span; mean over the fits.  Every round reads the
rows at least once (the first candidate's pass is left out), so the work
is a lower bound and the share cannot pass 100: a reading over 100 means
the counter misses rounds.  Nothing to read without a trace, without the
count, or where no init module ran."""


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
    names = ctx["cell"]["config_data"].get("init_modules", [])
    least, peaks = ctx["least"], ctx["peaks"]
    if "init_bytes" not in least:
        return None
    round_s = max(least["init_bytes"] / peaks["hbm_bytes_per_s"],
                  least["init_flops"] / peaks["flops_per_s"])
    shares = []
    for tree, fit in zip(trees, ctx["trace"]["fits"] if trees else []):
        rounds = (child(tree, "kmeans.init") or {}).get("attrs", {}).get(
            "rounds")
        device_s = sum(fit["modules"].get(n, 0.0) for n in names)
        if rounds is None or not device_s:
            return None
        shares.append(100.0 * rounds * round_s / device_s)
    return sum(shares) / len(shares) if shares else None
