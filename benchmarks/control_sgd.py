"""Read the incremental SGD cell's readings on the chip, many seeds in one
process.

    python benchmarks/control_sgd.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1] [--faults half_batch,...] [--set a.b=<json>]

``control_search.py`` for ``Incremental(SGDClassifier)`` trained by
passes: every reading goes through ``run.run_cell``, the path a benchmark
run times, with the cell's own estimator (``program``), the reference in
the next precision down (``control.<precision>``) or the passes with one
fault planted under them (``fault.<name>``) in the program's place.  Each
fault is one guarantee of the passes turned off, planted from outside on
what the public wrapper takes (no option in the program):

- ``half_batch``: every pass over the first half of the blocks (the
  table's first rows, as many as half the blocks hold);
- ``pass_dropped``: one pass fewer (39 for 40);
- ``state_reset``: every pass a new ``Incremental.fit``, so each call
  starts from zero and the fit is its last pass alone;
- ``order_ignored``: the blocks in their natural order on every pass
  (``shuffle_blocks=False``).

One JSON line a seed.  The limits in
``configs/incremental-sgd-higgs.json`` were set from these lines; a
benchmark run never calls this file.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness
from control import set_key

FAULTS = ("half_batch", "pass_dropped", "state_reset", "order_ignored")


def planted(make, fault: str):
    """``make`` (the configuration's factory) with one fault under the
    timed path: a factory of the same arguments."""
    from dask_ml_tpu.core.sharded import ShardedRows

    def head(rows, n):
        return ShardedRows(data=rows.data[:n], mask=rows.mask[:n], n_samples=n)

    def broken(**est_args):
        if fault == "pass_dropped":
            est_args = dict(est_args, passes=int(est_args["passes"]) - 1)
        if fault == "order_ignored":
            est_args = dict(est_args, shuffle_blocks=False)
        passes = make(**est_args)
        real = type(passes)

        class Broken(real):
            def fit(self, X, y):
                if fault == "half_batch":
                    chunk = int(est_args["chunk_size"])
                    n = -(-X.n_samples // chunk) // 2 * chunk
                    X, y = head(X, n), head(y, n)
                if fault != "state_reset":
                    return super().fit(X, y)
                for _ in range(self.passes):  # a new Incremental.fit each
                    one = real(self.estimator, 1, self.classes,
                               self.incremental).fit(X, y)
                self.incremental_, self.n_passes_ = one.incremental_, self.passes
                return self

        return Broken(passes.estimator, passes.passes, passes.classes,
                      passes.incremental)

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
