"""Read a k-means cell's readings on the chip, many seeds in one process.

    python benchmarks/control_kmeans.py --workload <cell> --seeds 1,2,3 \\
        [--faults half_batch,...] [--set generator_params.cluster_std=2.0 ...]

``control.py`` for a clustering: every reading goes through
``run.run_cell``, the path a benchmark run times, with the cell's own
estimator (``program``), the reference in the next precision down
(``control.<precision>``) or the estimator with one fault planted under it
(``fault.<name>``) in the program's place.  ``control.py :: planted``
alters ``coef_``, which a clustering has not, so the faults are planted
here, on what a ``KMeans`` fit leaves.  One JSON line a seed.  The limits
in ``configs/kmeans-blobs.json`` were set from these lines; a benchmark
run never calls this file.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness
from control import set_key

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def planted(real, fault: str):
    """``real`` (a ``KMeans`` class) with one fault under the timed path."""
    import jax.numpy as jnp

    from dask_ml_tpu.core.sharded import ShardedRows

    class Broken(real):
        def fit(self, X, y=None):
            if fault == "half_batch":  # half left out, the means over the rest
                n = X.n_samples // 2
                X = ShardedRows(data=X.data[:n], mask=X.mask[:n], n_samples=n)
            if fault == "state_unchanged":  # the start, returned as the answer
                self.max_iter = 0
            super().fit(X)
            if fault == "answer_altered":  # one number, where it is produced
                value = self.cluster_centers_
                self.cluster_centers_ = jnp.asarray(value).ravel().at[0].mul(
                    1.01).reshape(value.shape)
            return self

    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; have {FAULTS}")
    return Broken


def readings(cell, seed, *, devices, peaks, faults=(), rows_per_chip=None,
             controls=True):
    cfg = cell["config_data"]
    reference = harness.load_module("references", cfg["reference"])
    real = harness.import_attr(cfg["estimator"])

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
        out["fault." + fault] = read(planted(real, fault))
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
