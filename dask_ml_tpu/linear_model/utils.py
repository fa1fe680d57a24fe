"""Reference: ``dask_ml/linear_model/utils.py :: add_intercept``.

``add_intercept`` is kept for parity with the reference, as a public
helper: NO fit of this package calls it.  The GLM estimators hand the
solvers the caller's table and ``intercept=fit_intercept``, and the
solvers carry the intercept as a scalar beside the weights
(``solvers/families.py :: Family.split``), so no ``rows x (d + 1)`` copy
of the table is made.  Each call here counts in the registry as
``glm.intercept_columns``: 0 after any number of fits.
"""

from __future__ import annotations

import jax.numpy as jnp

from .. import obs as _obs
from ..core.sharded import ShardedRows


def binary_indicator(y, positive_class):
    """0/1 target for ``y == positive_class``, built where y lives
    (device labels never round-trip; the mask keeps pad rows inert).
    The ONE encoding shared by ``LogisticRegression.fit``'s OvR
    indicator, the packed C-sweep, and the sweep scorer — they must
    agree bit-for-bit or the packed grid path would score against a
    different encoding than it fit."""
    import numpy as np

    if isinstance(y, ShardedRows):
        return ShardedRows(
            data=(y.data == jnp.asarray(
                positive_class, y.data.dtype)).astype(jnp.float32),
            mask=y.mask, n_samples=y.n_samples,
        )
    return (np.asarray(y) == positive_class).astype(np.float32)


def add_intercept(X: ShardedRows) -> ShardedRows:
    """Append a ones column (zeroed on padded rows so solvers stay exact).
    A copy of the whole table; ``solver(X, y, intercept=True)`` is the
    same fit without it."""
    _obs.registry().counter("glm.intercept_columns").inc()
    ones = X.mask[:, None].astype(X.data.dtype)
    return ShardedRows(
        data=jnp.concatenate([X.data, ones], axis=1),
        mask=X.mask,
        n_samples=X.n_samples,
    )
