"""Least work of a consensus logistic solve on one device of several.

``per_round`` is ``logistic_pass.per_round``'s answer (one read of this
device's rows of X, one loss and one gradient), so the whole step's
shares count this cell as they count the one-chip cells.

``per_trial`` is what a line-search trial on the cached linear predictor
streams: four float32 vectors of a row's length (``eta``, the tangent
``u``, ``-y * mask`` and ``mask``; PERF.md section 3) and nothing of X.
A lower bound again: the four are read once a trial at least.
"""


def per_round(rows_on_device: int, features: int, est_args: dict) -> dict:
    return {"bytes": rows_on_device * features * 4,
            "flops": 4 * rows_on_device * features}


def per_trial(rows_on_device: int) -> dict:
    return {"bytes": 16 * rows_on_device}
