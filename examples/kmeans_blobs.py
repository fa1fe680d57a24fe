"""North-star #2: KMeans with k-means|| init and the fused Lloyd loop.

Each Lloyd round is one program: distance gemm on the MXU, masked
one-hot-gemm center reduce, psum across shards.
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
from sklearn.datasets import make_blobs  # noqa: E402

from dask_ml_tpu.cluster import KMeans  # noqa: E402
from dask_ml_tpu.core import shard_rows  # noqa: E402

X, y = make_blobs(n_samples=100_000, centers=8, n_features=16,
                  random_state=0)
km = KMeans(n_clusters=8, random_state=0).fit(shard_rows(X.astype(np.float32)))
print(f"inertia: {km.inertia_:.1f}  n_iter: {km.n_iter_}")
print("center norms:", np.linalg.norm(np.asarray(km.cluster_centers_), axis=1).round(2))
