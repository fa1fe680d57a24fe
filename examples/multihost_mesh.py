"""Scaling out: flat fleet meshes and the hierarchical DCN mesh.

Single-host programs run UNCHANGED on a fleet: form the process group
(`core.distributed.initialize`), build `global_mesh()`, and every psum
crosses hosts automatically (ICI within a slice, DCN between).  This
example demonstrates the mesh shapes in ONE process (the real
2-process form is `__graft_entry__.dryrun_multihost`, which spawns a
Gloo group over localhost):

- a flat `('data', 'model')` mesh — the recommended setup;
- a hierarchical `('dcn', 'data', 'model')` mesh with rows sharded over
  BOTH data-carrying axes — ADMM's consensus psums, TSQR's all_gather,
  and the pairwise ring all run natively on the `('dcn', 'data')` axis
  tuple (`core.mesh.data_axes`).
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from dask_ml_tpu.core import use_mesh  # noqa: E402
from dask_ml_tpu.core import distributed as dist  # noqa: E402
from dask_ml_tpu.core.mesh import Mesh  # noqa: E402
from dask_ml_tpu.linear_model import LogisticRegression  # noqa: E402

rng = np.random.RandomState(0)
X = rng.normal(size=(4000, 12)).astype(np.float32)
w = rng.normal(size=12)
y = (X @ w > 0).astype(np.float32)

# -- flat global mesh: what a fleet deployment uses by default
flat = dist.global_mesh()  # ('data', 'model') over all devices
with use_mesh(flat):
    Xs = dist.shard_rows_global(X, flat)
    ys = dist.shard_rows_global(y, flat)
    lr = LogisticRegression(solver="admm", max_iter=50).fit(Xs, ys)
    acc_flat = float(lr.score(Xs, ys))
print(f"flat mesh {dict(flat.shape)}: ADMM accuracy {acc_flat:.3f}")

# -- hierarchical mesh: explicit 'dcn' axis (2 slices x 4 devices on the
# CPU mesh, 2 x 2 on a four-chip host, 1 x 1 on one chip; on a real
# fleet global_mesh(hierarchical=True) derives it from the process group)
n_dev = len(jax.devices())
devs = np.array(jax.devices()).reshape(2 if n_dev % 2 == 0 else 1, -1, 1)
hmesh = Mesh(devs, ("dcn", "data", "model"))
with use_mesh(hmesh):
    Xh = dist.shard_rows_global(X, hmesh)
    yh = dist.shard_rows_global(y, hmesh)
    lrh = LogisticRegression(solver="admm", max_iter=50).fit(Xh, yh)
    acc_h = float(lrh.score(Xh, yh))
print(f"dcn mesh {dict(hmesh.shape)}: ADMM accuracy {acc_h:.3f}")
assert abs(acc_flat - acc_h) < 0.02
print("flat and hierarchical meshes agree")
