"""Incremental wrapper and SGD layer: the pass program's share of the HBM
roofline, in %.  Per traced fit: its passes (``incremental.pass`` spans)
x one pass's least bytes (``counts/<name>.py``: every real row's
features, its label and its mask read once; the program reads the
features twice, for the margins and then the gradient) over the chip's peak
bandwidth, as a share of the device seconds of the configuration's
``solve_modules`` (``jit__sgd_pass``) inside the fit's ``bench.fit``
span; mean over the fits.  The bytes are a lower bound, so the share
cannot pass 100.  Nothing to read without a trace, where the program
opens no such span (a parent commit) or where no pass module ran."""


def pass_spans(ctx):
    """The ``incremental.pass`` roots of the traced fits: the last
    ``passes`` x traced fits the program recorded (spans are recorded
    inside the profiler's session)."""
    if not ctx["trace"]:
        return []
    from dask_ml_tpu import obs

    passes = int(ctx["cell"]["config_data"]["estimator_args"]["passes"])
    roots = [r for r in obs.span_records()
             if r.name == "incremental.pass" and r.parent_id is None]
    return roots[-passes * len(ctx["trace"]["fits"]):]


def read(ctx):
    spans = pass_spans(ctx)
    names = ctx["cell"]["config_data"].get("solve_modules", [])
    fits = ctx["trace"]["fits"] if spans else []
    if not fits:
        return None
    least_s = (len(spans) / len(fits) * ctx["least"]["bytes"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    device_s = [sum(f["modules"].get(n, 0.0) for n in names) for f in fits]
    if not all(device_s):
        return None
    return sum(100.0 * least_s / d for d in device_s) / len(device_s)
