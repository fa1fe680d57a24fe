"""Least work of one outer round of a logistic solve on one device.

No solver can do a round without reading its rows of X once
(``rows * features * 4`` bytes of float32) and without one loss and one
gradient (``X @ w`` and ``X.T @ r``: ``2 * rows * features`` flops each).
A lower bound on purpose: it reads the same whatever implements the
solve, so the share it gives cannot pass 100%.
"""


def per_round(rows_on_device: int, features: int, est_args: dict) -> dict:
    return {"bytes": rows_on_device * features * 4,
            "flops": 4 * rows_on_device * features}
