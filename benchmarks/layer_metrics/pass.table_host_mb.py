"""Incremental wrapper and SGD layer: the bytes of table or labels one
pass brought to the host, in MB (10**6 bytes): ``table_host_mb`` on the
traced fits' ``incremental.pass`` spans, mean over the passes; 0 where
the pass runs on the resident table.  Nothing to read without a trace or
where the program opens no such span (a parent commit, which unshards
the table on every pass)."""


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
    values = [s.attrs.get("table_host_mb") for s in spans]
    if not values or None in values:
        return None
    return sum(values) / len(values)
