"""Search folds layer: the index and mask arrays a fit's folds made on
the host, in MB (10**6 bytes): the splitter's own index arrays and the
``int32`` index and ``float32`` mask ``_split.py :: _take`` sends to the
device for every sharded array and side.  0 where folds are cut as slabs
on the device (an unshuffled ``KFold`` of this package on sharded rows).

A count ``_search.py`` puts on ``search.fold`` as ``host_index_bytes``
from the arrays' own sizes; summed over a fit's folds, mean over the
traced fits.  Nothing to read without a trace or where the span or the
count is missing (a parent commit)."""


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
    per_fit = []
    for tree in fit_trees(ctx):
        values = [s["attrs"].get("host_index_bytes")
                  for s in children(tree, "search.fold")]
        if not values or None in values:
            return None
        per_fit.append(sum(values) / 1e6)
    return sum(per_fit) / len(per_fit) if per_fit else None
