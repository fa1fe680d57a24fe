"""Read a search cell's readings on the chip, many seeds in one process.

    python benchmarks/control_search.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1] [--faults scored_on_train,...] [--set a.b=<json>]

``control_kmeans.py`` for a cross-validated search: every reading goes
through ``run.run_cell``, the path a benchmark run times, with the cell's
own estimator (``program``), the reference in the next precision down
(``control.<precision>``) or the search with one fault planted under it
(``fault.<name>``) in the program's place.  Each fault is one guarantee of
a search turned off, planted from outside on what the public search takes
and leaves (no option in the program):

- ``scored_on_train``: every split scored on rows it was fitted on (the
  held-out slab swapped for the head of the train slab, as many rows);
- ``fold_dropped``: the last fold never run: its scores reported as the
  mean of the others', so that the mean is the mean over two folds;
- ``half_batch``: the whole search on the first half of the rows;
- ``refit_skipped``: no fit of all rows: the chosen ``C``'s fit of one
  fold's train rows (the first two thirds) reported as ``coef_``;
- ``wrong_choice``: the choice's sign turned: the candidate with the
  LOWEST mean score returned as ``best_index_``, and refitted on all rows
  (so that nothing but the choice is at fault).

One JSON line a seed.  The limits in ``configs/gridsearch-c-higgs.json``
were set from these lines; a benchmark run never calls this file.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as harness
from control import set_key

FAULTS = ("scored_on_train", "fold_dropped", "half_batch", "refit_skipped",
          "wrong_choice")


def planted(make, fault: str):
    """``make`` (the configuration's factory) with one fault under the
    timed path: a factory of the same arguments."""
    from dask_ml_tpu.base import clone
    from dask_ml_tpu.core.sharded import ShardedRows
    from dask_ml_tpu.model_selection import _search

    def head(rows, n):
        return ShardedRows(data=rows.data[:n], mask=rows.mask[:n], n_samples=n)

    def broken(**est_args):
        search = make(**est_args)
        real = type(search)

        class Broken(real):
            def fit(self, X, y):
                if fault == "half_batch":
                    X, y = head(X, X.n_samples // 2), head(y, y.n_samples // 2)
                if fault == "scored_on_train":
                    cut = _search._fold_slabs

                    def on_train(X, y, lo, hi):
                        # the held-out side becomes the train side's
                        # first rows, as many
                        Xtr, ytr, Xte, _ = cut(X, y, lo, hi)
                        k = Xte.n_samples
                        return Xtr, ytr, head(Xtr, k), head(ytr, k)

                    _search._fold_slabs = on_train
                    try:
                        super().fit(X, y)
                    finally:
                        _search._fold_slabs = cut
                else:
                    super().fit(X, y)
                if fault == "refit_skipped":
                    n = 2 * X.n_samples // 3
                    self.best_estimator_ = clone(self.estimator).set_params(
                        **self.best_params_).fit(head(X, n), head(y, n))
                if fault == "wrong_choice":
                    self.best_index_ = int(np.argmin(
                        self.cv_results_["mean_test_score"]))
                    self.best_params_ = self.cv_results_["params"][
                        self.best_index_]
                    self.best_estimator_ = clone(self.estimator).set_params(
                        **self.best_params_).fit(X, y)
                return self

            if fault == "fold_dropped":
                @property
                def split_test_scores_(self):
                    scores = real.split_test_scores_.fget(self).copy()
                    scores[:, -1] = scores[:, :-1].mean(axis=1)
                    return scores

        return Broken(**search.get_params(deep=False))

    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; have {FAULTS}")
    return broken


def readings(cell, seed, *, devices, peaks, faults=(), rows_per_chip=None,
             controls=True):
    cfg = cell["config_data"]
    reference = harness.load_module("references", cfg["reference"])
    make = harness.import_attr(cfg["estimator"])

    def read(estimator):
        line = harness.run_cell(
            cell, seed, 0.0, False, devices=devices, peaks=peaks,
            rows_per_chip=rows_per_chip, estimator=estimator)
        return {k: v for k, (v, _limit) in line["checks"].items()} | {
            "passes": line["correct"]}

    out = {"seed": seed, "program": read(None)}
    for precision in cfg.get("controls", []) if controls else []:
        out["control." + precision] = read(
            reference.control_estimator(precision))
    for fault in faults:
        out["fault." + fault] = read(planted(make, fault))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None,
                    help="the seeds that also read the controls and the "
                         "faults (default: all)")
    ap.add_argument("--faults", default="")
    ap.add_argument("--set", action="append", default=[], dest="sets")
    args = ap.parse_args(argv)
    sys.path.insert(0, harness.ROOT)
    cell = harness.load_cell(args.workload)
    for assignment in args.sets:
        set_key(cell["config_data"], assignment)
    peaks = harness.load_json(harness.HERE, "peaks.json")["peaks"]
    import jax

    devices = harness.require_chip(jax, int(cell["chips"]), peaks)
    faults = [f for f in args.faults.split(",") if f]
    seeds = [int(s) for s in args.seeds.split(",")]
    full = (set(seeds) if args.control_seeds is None
            else {int(s) for s in args.control_seeds.split(",")})
    for seed in seeds:
        print(json.dumps(readings(
            cell, seed, devices=devices, peaks=peaks,
            faults=faults if seed in full else (),
            controls=seed in full)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
