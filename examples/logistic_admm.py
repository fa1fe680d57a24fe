"""North-star #1: LogisticRegression(solver='admm') on sharded rows.

The whole ADMM solve — per-shard L-BFGS subproblems inside shard_map,
psum consensus, residual-based stopping — compiles to ONE XLA program
(reference: dask_glm pays a scheduler round-trip per outer iteration).
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

import numpy as np  # noqa: E402

from dask_ml_tpu.core import shard_rows  # noqa: E402
from dask_ml_tpu.linear_model import LogisticRegression  # noqa: E402

rng = np.random.RandomState(0)
n, d = 200_000, 28  # HIGGS-shaped columns
X = rng.normal(size=(n, d)).astype(np.float32)
w_true = rng.normal(size=d)
y = (X @ w_true + 0.3 * rng.normal(size=n) > 0).astype(np.float32)

sX, sy = shard_rows(X), shard_rows(y)  # rows pad+shard over the mesh
clf = LogisticRegression(solver="admm", C=1e4, max_iter=30).fit(sX, sy)
print(f"train accuracy: {clf.score(sX, sy):.4f}")
print(f"n_iter_: {clf.n_iter_}  coef | {np.asarray(clf.coef_)[:4].round(3)}")
