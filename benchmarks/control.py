"""Read a cell's two readings on the chip, many seeds in one process.

    python benchmarks/control.py --workload <cell> --seeds 1,2,3 \\
        [--faults half_batch,...] [--set estimator_args.C=1.0 ...]

Every reading goes through ``run.run_cell``, the path a benchmark run
times: the table made from the seed, ``shard_rows``, a warm-up fit, one
fit of the window, the plain reference, ``judge``.  What changes is what
stands in the program's place:

- ``program``: nothing; the cell's own estimator (the lower reading);
- ``control.<precision>``: the reference computed in the next precision
  down (the reference module's ``control_estimator``), for every control
  the configuration lists (the upper reading);
- ``fault.<name>``: the cell's estimator with one fault planted under it
  (``planted``), read at the cell's own size.

``--set`` changes one key of the configuration for this reading only (a
JSON value), e.g. another arm of the program on the same tables.  One JSON
line a seed.  The limits in ``configs/*.json`` were set from these lines;
a benchmark run never calls this file.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def planted(real, fault: str):
    """``real`` (an estimator class) with one fault under the timed path."""
    import jax.numpy as jnp

    from dask_ml_tpu.core.sharded import ShardedRows

    def head(rows):
        if rows is None:
            return None
        n = rows.n_samples // 2
        return ShardedRows(data=rows.data[:n], mask=rows.mask[:n], n_samples=n)

    class Broken(real):
        def fit(self, X, y=None):
            if fault == "half_batch":  # half left out, the mean over the rest
                X, y = head(X), head(y)
            super().fit(X, y) if y is not None else super().fit(X)
            value = self.coef_
            if fault == "state_unchanged":  # the start, returned as the answer
                self.coef_ = jnp.zeros_like(value)
            if fault == "answer_altered":  # one number, where it is produced
                self.coef_ = jnp.asarray(value).ravel().at[0].mul(
                    1.01).reshape(value.shape)
            return self

    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; have {FAULTS}")
    return Broken


def readings(cell, seed, *, devices, peaks, faults=(), rows_per_chip=None):
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
    for precision in cfg.get("controls", []):
        out["control." + precision] = read(
            reference.control_estimator(precision))
    for fault in faults:
        out["fault." + fault] = read(planted(real, fault))
    return out


def set_key(cfg: dict, assignment: str):
    """``a.b.c=<json>`` into the configuration, for this process only."""
    path, _, value = assignment.partition("=")
    *parents, leaf = path.split(".")
    for key in parents:
        cfg = cfg[key]
    cfg[leaf] = json.loads(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
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
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, devices=devices, peaks=peaks,
                                  faults=faults)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
