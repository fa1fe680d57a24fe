"""North-star #5: HyperbandSearchCV over the device-native SGDClassifier.

Homogeneous candidate configs pack into ONE vmapped program per training
round (DISPATCH_STATS shows the packed dispatches); schedules match the
reference's bracket math exactly (metadata == metadata_).
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
from scipy.stats import loguniform  # noqa: E402

from dask_ml_tpu.core import shard_rows  # noqa: E402
from dask_ml_tpu.linear_model import SGDClassifier  # noqa: E402
from dask_ml_tpu.model_selection import HyperbandSearchCV  # noqa: E402

rng = np.random.RandomState(0)
X = rng.normal(size=(20_000, 12)).astype(np.float32)
y = (X @ rng.normal(size=12) > 0).astype(np.float32)

search = HyperbandSearchCV(
    SGDClassifier(tol=None),
    # a continuous distribution: Hyperband samples as many configs as
    # its largest bracket asks for without exhausting a finite grid
    {"alpha": loguniform(1e-6, 1e-1), "eta0": [0.01, 0.1, 0.5]},
    max_iter=27, random_state=0, verbose=True,
)
search.fit(shard_rows(X), shard_rows(y), classes=[0.0, 1.0])
print(f"best: {search.best_params_}  score={search.best_score_:.4f}")
print(f"budget: {search.metadata_['partial_fit_calls']} partial_fit calls "
      f"across {search.metadata_['n_models']} models")
