"""Search folds layer: what a fit's folds cost before their sweeps can
start (``model_selection/_search.py``).  On the packed path a
``search.fold`` span holds the cut of the fold (one program of slices
for an unshuffled ``KFold`` on sharded rows, ``_split.py ::
_fold_slabs``; index arrays made on the host and a gather for every
other splitter) and the check that the fold's labels are the two classes,
which is where the host first waits for what the cut made: so the cut's
device time is inside it.

Read from the program's own spans (``dask_ml_tpu/obs/spans.py``, live
while the profiler session of a ``--trace 1`` run is on): the durations
of the ``search.fold`` spans of each traced fit's ``search.fit`` tree,
summed over the fit's folds, mean over those fits, in ms.  Nothing to
read without a trace or where the program opens no such span (a parent
commit that has none)."""


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
    per_fit = [[s["dur_s"] for s in children(t, "search.fold")]
               for t in fit_trees(ctx)]
    if not per_fit or not all(per_fit):
        return None
    return 1e3 * sum(map(sum, per_fit)) / len(per_fit)
