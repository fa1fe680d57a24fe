"""Incremental wrapper and SGD layer: the wall of one ``partial_fit`` pass
over the resident table (``linear_model/_sgd_pass.py :: run``), in ms:
the durations of the traced fits' ``incremental.pass`` spans, mean over
the passes.  Nothing waits on the device inside a pass, so this is the
host's part of a pass (its checks and the dispatch of ``sgd.pass``); the
device's is ``sgd.step_us``.  Nothing to read without a trace or where
the program opens no such span (a parent commit)."""


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
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)
