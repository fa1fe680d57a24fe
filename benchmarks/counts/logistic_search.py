"""Least work of one fold of a cross-validated search over ``C`` of a
logistic model, on one device.

No search can do a fold without reading the fold's train rows once for a
loss and a gradient (``4 * rows_train * features`` flops; the lanes share
X, so one read serves every candidate: that is what packing buys) and
without reading its held-out rows once to score them (``2 * rows_test *
features`` flops a candidate): together one read of this device's rows
of X.  ``per_round`` is handed no iteration count, so it states a single
read of the train slab; ``layer_metrics/sweep.hbm_roof_pct.py`` counts
one for each iteration of the slowest lane, from ``train_bytes``.  Lower
bounds on purpose: the shares they give cannot pass 100%.
"""


def per_round(rows_on_device: int, features: int, est_args: dict) -> dict:
    folds = int(est_args["cv"])
    lanes = len(est_args["param_grid"]["C"])
    rows_test = rows_on_device // folds
    rows_train = rows_on_device - rows_test
    return {"bytes": rows_on_device * features * 4,
            "flops": (4 * rows_train + 2 * rows_test * lanes) * features,
            "train_bytes": rows_train * features * 4,
            "test_bytes": rows_test * features * 4}
