"""Read a sharded cell's readings on the chips, many seeds in one process.

    python benchmarks/control_consensus.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1] [--faults shard_dropped,no_exchange,half_batch] \\
        [--set estimator_args.C=1.0 ...]

``control.py`` for a fit whose table lies over several chips.  The
guarantee of such a fit is that every shard's rows count in the answer,
and the faults planted here are the ways a consensus can break it:

- ``shard_dropped``: the fit sees the rows of all chips but the last
  (what a lost exchange with one shard would answer);
- ``no_exchange``: the first chip's rows alone (what a consensus that
  never met would answer on shard 0);
- ``half_batch``: the first half of the rows, which are the rows of the
  first half of the chips (``control.py``'s fault of that name slices
  the table, and a slice of a table sharded over four chips gathers it
  whole: 32 GB asked at 250M rows, my chip run, PR 34).

All are planted from outside, with no option in the program: the
estimator is handed the SAME device buffers, those of the chips it may
see, as a table on a mesh of just those chips (``use_mesh``, the
program's public setting), so no copy of the table is made and the full
share still fits.  ``control.py``'s other faults are served from there.
Every reading goes through ``run.run_cell``, the path a benchmark run
times; one JSON line a seed.  The limits in
``configs/admm-higgs-250m.json`` were set from these lines; a benchmark
run never calls this file.
"""

from __future__ import annotations

import argparse
import json
import sys

import control
import run as harness

#: fault -> the chips of ``n`` the fit may see
FAULTS = {"shard_dropped": lambda n: n - 1, "no_exchange": lambda n: 1,
          "half_batch": lambda n: n // 2}


def on_chips(rows, mesh):
    """The rows of ``rows`` (a ``ShardedRows``) that lie on ``mesh``'s
    chips, as a ``ShardedRows`` on that mesh: the same buffers.  The
    chips kept must hold the first runs of rows, in their order."""
    import jax

    from dask_ml_tpu.core.sharded import ShardedRows, row_sharding

    def view(array):
        parts = {s.device: s for s in array.addressable_shards}
        held = [parts[d] for d in mesh.devices.flat]
        starts = [s.index[0].start or 0 for s in held]
        n = sum(s.data.shape[0] for s in held)
        if starts != [i * n // len(held) for i in range(len(held))]:
            raise ValueError(f"the chips of {mesh} do not hold the first "
                             f"runs of rows in order: {starts}")
        return jax.make_array_from_single_device_arrays(
            (n,) + array.shape[1:], row_sharding(mesh, array.ndim),
            [s.data for s in held])

    data = view(rows.data)
    return ShardedRows(data=data, mask=view(rows.mask),
                       n_samples=min(rows.n_samples, data.shape[0]))


def planted(real, fault: str):
    """``real`` (an estimator class) fitted on the chips the fault
    leaves it."""
    from dask_ml_tpu.core import device_mesh, use_mesh
    from dask_ml_tpu.core.mesh import data_axes_size

    if fault not in FAULTS:
        return control.planted(real, fault)

    class Broken(real):
        def fit(self, X, y=None):
            chips = data_axes_size()
            if chips < 2:
                raise ValueError(f"{fault} needs a table over several "
                                 f"chips; the mesh has {chips}")
            mesh = device_mesh(FAULTS[fault](chips))
            with use_mesh(mesh):
                return super().fit(
                    on_chips(X, mesh),
                    None if y is None else on_chips(y, mesh))

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
        control.set_key(cell["config_data"], assignment)
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
