"""Least work of one ``partial_fit`` pass of a logistic SGD over the rows
on one device: one step a block.

A step needs each real row's ``features`` float32 once: row ``i``'s
part of the gradient, ``r(m_i) x_i``, needs its own margin ``m_i = x_i .
w + c`` and no other row's, so one read of a row can serve both
products (the program reads a block twice: the margins, then the
gradient).  Its label and its mask are read once (``4 + 4`` bytes).
FLOPs: the two products, ``2 * features`` each a row.  Lower bounds on
purpose: the shares they give cannot pass 100%, whatever reads the rows.
"""


def per_round(rows_on_device: int, features: int, est_args: dict) -> dict:
    block = int(est_args["chunk_size"])
    return {"bytes": rows_on_device * (features * 4 + 4 + 4),
            "flops": 4 * rows_on_device * features,
            "steps": -(-rows_on_device // block)}
