"""ADMM consensus: the solve's share of the HBM roofline with the line
search's trials counted, in %.  Per traced fit: ``passes`` x one read of
this device's rows of X (``per_round``) + ``trials`` x the four row
vectors a trial streams (``per_trial``; both in the configuration's
``counts/<name>.py``, both counts on ``glm.solve``: the slowest shard's,
which is the one the round waits for) over the chip's peak bandwidth, as
a share of the device seconds of the configuration's ``solve_modules``
inside that fit's ``bench.fit`` span (the mean over the devices); mean
over the fits.  Every byte counted is one the program's own counters say
a device streamed, each array counted once an operation, so the share is
a lower bound and cannot pass 100.  (``solve.hbm_roof_pct`` leaves the
trials out.)  Nothing to read without a trace, without the counts, where
the count function states no ``per_trial`` or where no solve module
ran."""

import importlib.util
import os


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


def per_trial(cfg):
    """The configuration's ``counts/<name>.py :: per_trial``, or None."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "counts", cfg["counts"] + ".py")
    spec = importlib.util.spec_from_file_location("consensus_counts", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, "per_trial", None)


def read(ctx):
    trees = fit_trees(ctx)
    cfg = ctx["cell"]["config_data"]
    trial = per_trial(cfg)
    if trial is None:
        return None
    names = cfg.get("solve_modules", [])
    shares = []
    for tree, fit in zip(trees, ctx["trace"]["fits"] if trees else []):
        attrs = (child(tree, "glm.solve") or {}).get("attrs", {})
        passes, trials = attrs.get("passes"), attrs.get("trials")
        device_s = sum(fit["modules"].get(n, 0.0) for n in names)
        if passes is None or trials is None or not device_s:
            return None
        # this device's rows as the program's root span states them
        rows = tree["attrs"]["rows"] // tree["attrs"]["chips"]
        streamed = (passes * ctx["least"]["bytes"]
                    + trials * trial(rows)["bytes"])
        least_s = streamed / ctx["peaks"]["hbm_bytes_per_s"]
        shares.append(100.0 * least_s / device_s)
    return sum(shares) / len(shares) if shares else None
