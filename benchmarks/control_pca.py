"""Read a PCA cell's readings on the chip, many seeds in one process.

    python benchmarks/control_pca.py --workload <cell> --seeds 1,2,3 \\
        [--faults half_batch,...] [--set generator_params.s_min=0.5 ...]

``control.py`` for a decomposition: every reading goes through
``run.run_cell``, the path a benchmark run times, with the cell's own
estimator (``program``), the reference in the next precision down
(``control.<precision>``) or the estimator with one fault planted under it
(``fault.<name>``) in the program's place.  ``control.py :: planted``
alters ``coef_``, which a PCA has not, so the faults are planted here, on
what a ``PCA`` fit does and leaves.  One JSON line a seed.  The limits in
``configs/pca-tsqr.json`` were set from these lines; a benchmark run never
calls this file.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness
from control import set_key

FAULTS = ("half_batch", "uncentred", "answer_altered", "repair_skipped")


def planted(real, fault: str):
    """``real`` (a ``PCA`` class) with one fault under the timed path."""
    import jax
    import jax.numpy as jnp

    from dask_ml_tpu.core.sharded import ShardedRows
    from dask_ml_tpu.decomposition import pca as program

    factor = program.factor_r

    def uncentred(X, center=None, **kw):
        """The mean reported, and not subtracted."""
        _, mean, _ = factor(X, center="mean", **kw)
        r, _, info = factor(X, center=None, **kw)
        return r, mean, info

    @jax.jit
    def gram(x, mask, mean):
        z = (x - mean) * mask[:, None]
        return jnp.matmul(z.T, z, precision=jax.lax.Precision.HIGHEST)

    def repair_skipped(X, center=None, **kw):
        """CholeskyQR without its second pass and without care in its
        sums: R is the Cholesky factor of the centred Gram matrix, taken
        as one float32 product that contracts over all the rows."""
        _, mean, info = factor(X, center="mean", **kw)
        return jnp.linalg.cholesky(gram(X.data, X.mask, mean)).T, mean, info

    class Broken(real):
        def fit(self, X, y=None):
            if fault == "half_batch":  # half left out, the PCA of the rest
                n = X.n_samples // 2
                X = ShardedRows(data=X.data[:n], mask=X.mask[:n], n_samples=n)
            swapped = {"uncentred": uncentred,
                       "repair_skipped": repair_skipped}.get(fault)
            if swapped is not None:
                program.factor_r = swapped
            try:
                super().fit(X)
            finally:
                program.factor_r = factor
            if fault == "answer_altered":  # one component, x 1.01
                self.components_ = jnp.asarray(self.components_).at[0].mul(
                    1.01)
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
