"""Least work of one pass of an exact PCA fit over one device's rows.

An exact PCA of a table cannot be had without the table's centred Gram
matrix, ``2 * rows * features ** 2`` flops, and not without reading the
rows.  The program reads them three times (the mean; the Gram; the
CholeskyQR2 repair, which whitens the rows and takes their Gram again),
and the harness multiplies one round's work by the passes the fit counts
(``n_passes_``): so ``bytes`` is one read of the rows (``rows * features *
4`` bytes of float32) and ``flops`` the one Gram's divided by those three
passes, and ``rounds x per_round`` is three reads and one Gram, the whole
fit's least work as this route makes it.

``factor_passes`` lists the three passes' own least ``[bytes, flops]``
(the mean: one add a number; the Gram; the repair: the whitening product
and its Gram), which ``factor.roof_pct`` sums pass by pass.  Lower bounds
on purpose: they read the same whatever implements a pass, so the shares
they give cannot pass 100%.
"""

PASSES = 3


def per_round(rows_on_device: int, features: int, est_args: dict) -> dict:
    table = rows_on_device * features * 4
    gram = 2 * rows_on_device * features * features
    return {"bytes": table, "flops": gram // PASSES,
            "factor_passes": [[table, rows_on_device * features],
                              [table, gram], [table, 2 * gram]]}
