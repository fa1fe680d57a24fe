"""Out-of-core training: a block stream through partial_fit.

The model lives ON DEVICE; blocks stream through it and are dropped —
only one block is ever resident, so the total stream can exceed device
memory (no chip reading of a streamed fit exists yet: PERF.md section 7
row 8).
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

from dask_ml_tpu.datasets import stream_classification_blocks  # noqa: E402
from dask_ml_tpu.linear_model import SGDClassifier  # noqa: E402

clf = SGDClassifier(random_state=0)
n_blocks, rows = 20, 4096
for i, (Xb, yb) in enumerate(
    stream_classification_blocks(n_blocks, rows, 32, seed=0)
):
    clf.partial_fit(Xb, yb, classes=[0.0, 1.0])
print(f"streamed {n_blocks * rows} rows through a device-resident model")
print(f"steps taken: {clf.t_:.0f}")

# --- the same loop fed from DISK through the native C++ loader --------
# (how a real out-of-core dataset flows: file -> parser -> device; the
# parser sustains ~363 MB/s on one core, and the prefetch ring keeps
# parsing overlapped with device compute)
import tempfile  # noqa: E402

from dask_ml_tpu.io import stream_csv_blocks  # noqa: E402

rng = np.random.RandomState(0)
with tempfile.NamedTemporaryFile("w", suffix=".csv", delete=False) as f:
    for _ in range(8):
        block = rng.normal(size=(2048, 9)).astype(np.float32)
        # last column is the label
        block[:, -1] = (block[:, 0] > 0).astype(np.float32)
        f.write("\n".join(
            ",".join(f"{v:.6g}" for v in row) for row in block) + "\n")
    csv_path = f.name

clf2 = SGDClassifier(random_state=0)
n_rows = 0
for blk in stream_csv_blocks(csv_path, 4096):
    Xb, yb = blk[:, :-1], blk[:, -1]
    clf2.partial_fit(Xb, yb, classes=[0.0, 1.0])
    n_rows += blk.shape[0]
pathlib.Path(csv_path).unlink()
print(f"loader-fed: {n_rows} rows from disk, steps {clf2.t_:.0f}")
