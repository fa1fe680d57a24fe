"""``Incremental(SGDClassifier)`` trained by several ``partial_fit``
passes, in the shape the harness builds.

``run.py`` makes ``Estimator(**estimator_args)`` from a configuration's
JSON and ends a fit at the fetch; a user of ``Incremental`` trains by
repeated passes over the same blocks (dask-ml's "Incremental Learning":
``fit`` once, then ``partial_fit``).  ``make`` composes the two public
constructors from plain arguments, and ``fit`` is that loop: the first
pass ``Incremental.fit(X, y, classes=...)``, every later one
``partial_fit(X, y)``.  The names the harness fetches read what the
passes left and compute nothing.  The first pass of every fit must be
one dispatch of the pass program, or the fit raises.
"""

from __future__ import annotations

from dask_ml_tpu import diagnostics
from dask_ml_tpu.linear_model import SGDClassifier
from dask_ml_tpu.wrappers import Incremental

#: the program one pass is (the configuration's ``solve_modules`` names
#: it as XLA does, ``jit__sgd_pass``)
PROGRAM = "sgd.pass"


def _dispatches() -> int:
    """Calls of :data:`PROGRAM` so far, by the program cache's books."""
    book = diagnostics.program_report()["programs"].get(PROGRAM, {})
    return sum(book.get(k, 0) for k in ("hits", "misses", "bypass",
                                        "fallback"))


class IncrementalSGD:
    """``passes`` passes of ``Incremental(SGDClassifier(**estimator),
    **incremental)`` over the same table, in one ``fit``."""

    def __init__(self, estimator: dict, passes: int, classes, incremental):
        self.estimator = estimator
        self.passes = passes
        self.classes = classes
        self.incremental = incremental

    def fit(self, X, y):
        self.incremental_ = Incremental(SGDClassifier(**self.estimator),
                                        **self.incremental)
        before = _dispatches()
        self.incremental_.fit(X, y, classes=self.classes)
        if _dispatches() != before + 1:
            # a pass the configuration does not describe (one that reads
            # the table back to the host and streams it in blocks takes
            # minutes a fit): the run stops here, at its first pass
            raise RuntimeError(
                f"a pass over the resident table is one dispatch of "
                f"{PROGRAM!r}; this one made {_dispatches() - before}")
        for _ in range(self.passes - 1):
            self.incremental_.partial_fit(X, y)
        self.n_passes_ = self.passes
        return self

    @property
    def coef_(self):
        return self.incremental_.estimator_.coef_

    @property
    def intercept_(self):
        return self.incremental_.estimator_.intercept_

    @property
    def t_(self):
        """Steps taken: one a block, counted by the program's state."""
        return self.incremental_.estimator_.t_


def make(estimator: dict, passes: int, classes, **incremental):
    """``Incremental(SGDClassifier(**estimator), **incremental)`` trained
    by ``passes`` passes, every other argument of either at its
    default."""
    return IncrementalSGD(estimator, int(passes), classes, incremental)
