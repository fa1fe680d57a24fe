"""``GridSearchCV`` over a GLM's grid, in the shape the harness builds.

``run.py`` makes ``Estimator(**estimator_args)`` from a configuration's
JSON, and a search holds an estimator, which JSON cannot.  ``make``
composes the two public constructors from plain arguments, and the class
it returns adds nothing to the search but names for what the harness
fetches as arrays: each reads what ``fit`` left and computes nothing.
"""

from __future__ import annotations

import numpy as np

from dask_ml_tpu.linear_model import LogisticRegression
from dask_ml_tpu.model_selection import GridSearchCV


class GridSearchC(GridSearchCV):
    """The search, with its results under the names of arrays."""

    @property
    def split_test_scores_(self):
        """(candidates, folds): ``cv_results_["split<i>_test_score"]``."""
        return np.array([self.cv_results_[f"split{i}_test_score"]
                         for i in range(self.n_splits_)]).T

    @property
    def coef_(self):
        return self.best_estimator_.coef_

    @property
    def intercept_(self):
        return self.best_estimator_.intercept_

    @property
    def n_iter_(self):
        return self.best_estimator_.n_iter_


def make(estimator: dict, param_grid: dict, cv: int):
    """``GridSearchCV(LogisticRegression(**estimator), param_grid, cv=cv)``,
    every other argument of either at its default."""
    return GridSearchC(LogisticRegression(**estimator), param_grid, cv=cv)
