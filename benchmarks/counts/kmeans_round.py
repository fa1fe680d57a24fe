"""Least work of one round of a k-means fit on one device.

A Lloyd round cannot be done without reading its rows of X once
(``rows * features * 4`` bytes of float32), without the rows' products
with the ``k`` centres for the distances (``2 * rows * features * k``
flops) and without as many again for the per-cluster sums.  A k-means||
round cannot be done without one read of X and the products with its at
most ``cap = max(4 * oversampling_factor * k, 8)`` new candidates
(``2 * rows * features * cap`` flops): those are ``init_bytes`` and
``init_flops``.  Lower bounds on purpose: they read the same whatever
implements the round, so the shares they give cannot pass 100%.
"""


def per_round(rows_on_device: int, features: int, est_args: dict) -> dict:
    k = int(est_args.get("n_clusters", 8))
    cap = max(4 * int(est_args.get("oversampling_factor", 2)) * k, 8)
    table = rows_on_device * features * 4
    return {"bytes": table, "flops": 4 * rows_on_device * features * k,
            "init_bytes": table,
            "init_flops": 2 * rows_on_device * features * cap}
