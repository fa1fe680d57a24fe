"""Incremental wrapper and SGD layer: the device time of one SGD step, in
microseconds: per traced fit, the device seconds of the configuration's
``solve_modules`` (the pass program, ``jit__sgd_pass``) inside its
``bench.fit`` span over the steps the fit took (the ``blocks`` of its
``incremental.pass`` spans); mean over the fits.  Nothing to read
without a trace, where the program opens no such span (a parent commit)
or where no pass module ran."""


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
    steps = [s.attrs.get("blocks") for s in spans]
    if not fits or None in steps:
        return None
    per_fit = sum(steps) / len(fits)
    device_s = [sum(f["modules"].get(n, 0.0) for n in names) for f in fits]
    if not all(device_s):
        return None
    return 1e6 * sum(device_s) / len(device_s) / per_fit
