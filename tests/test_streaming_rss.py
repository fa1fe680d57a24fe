"""Bounded-RSS out-of-core streaming — the HOST half of the >HBM
contract (reference: ``dask_ml/_partial.py :: fit``, SURVEY.md §7 hard
part (b): the whole point of the reference is fitting data that doesn't
fit).

The native streaming session (``native/loader.cpp :: dmlt_stream_*``) is
WINDOWED: the file moves through a ~32 MB window and is never fully
resident, so a dataset far beyond any memory budget streams through
``partial_fit`` with peak RSS bounded by (jax baseline + window + ring
blocks) — NOT by file size.  Measured baseline of the child pipeline
(jax-cpu + loader + SGD) is ~430 MB; the 1200 MB bound fails loudly if
the session ever regresses to whole-file reads (the pre-round-5 design
malloc'd the entire file: a 2 GB stream would peak >2.4 GB).

Runs in a subprocess so ``ru_maxrss`` measures exactly this pipeline,
not the test session's accumulated peak.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from dask_ml_tpu.linear_model import SGDClassifier
from dask_ml_tpu.io import stream_csv_blocks

def peak_mb():
    # VmHWM, NOT ru_maxrss: a forked child's ru_maxrss includes the
    # PARENT'S resident set at fork time (the COW window before exec),
    # so under a fat parent — a pytest session 790 tests deep, ~4 GB —
    # ru_maxrss reports the parent's peak no matter what this process
    # does.  VmHWM belongs to the post-exec mm and measures only us.
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmHWM"):
                return int(line.split()[1]) / 1024.0
    raise RuntimeError("VmHWM not found")

path = sys.argv[1]
clf = SGDClassifier(random_state=0)
n = 0
first_peak = None
for blk in stream_csv_blocks(path, 65536):
    clf.partial_fit(
        blk[:, :-1], (blk[:, -1] > 0.5).astype(np.float32),
        classes=[0.0, 1.0],
    )
    n += blk.shape[0]
    if first_peak is None:
        first_peak = peak_mb()  # baseline: jax + loader + one block
print(json.dumps({"rows": n, "steps": float(clf.t_),
                  "peak_mb": peak_mb(), "first_peak_mb": first_peak}))
"""


def _write_big_csv(path, target_gb: float) -> int:
    """Write ~target_gb of numeric CSV by repeating one formatted block
    (generation must be disk-bound, not Python-format-bound).  Returns
    the exact row count."""
    rng = np.random.RandomState(7)
    block = rng.rand(4000, 16).astype(np.float32)
    txt = "\n".join(
        ",".join(f"{v:.6g}" for v in row) for row in block
    ) + "\n"
    reps = int(target_gb * 1e9) // len(txt) + 1
    with open(path, "w") as f:
        for _ in range(reps):
            f.write(txt)
    return 4000 * reps


def _stream_in_child(path: str) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, path],
        capture_output=True, text=True, timeout=1800, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _kernel_has_vmhwm() -> bool:
    try:
        with open("/proc/self/status") as f:
            return "VmHWM" in f.read()
    except OSError:
        return False


class TestBoundedRSSStreaming:
    def test_2gb_stream_bounded_rss(self, tmp_path):
        if not _kernel_has_vmhwm():
            pytest.skip("kernel does not expose VmHWM (sandboxed /proc)")
        p = tmp_path / "big.csv"
        rows = _write_big_csv(p, 2.0)
        try:
            res = _stream_in_child(str(p))
        finally:
            p.unlink()
        assert res["rows"] == rows
        assert res["steps"] > 0  # the model actually stepped
        # two invariants: (a) RSS growth after the first block stays
        # bounded — the stream must not ACCUMULATE (measured ~40 MB;
        # generous margin for allocator variance under a loaded suite);
        # (b) absolute peak far below the ~2430 MB a whole-file-resident
        # session would need for this ~2000 MB file.
        assert res["peak_mb"] - res["first_peak_mb"] < 500, res
        assert res["peak_mb"] < 1500, res

    @pytest.mark.skipif(
        not os.environ.get("DASK_ML_TPU_TEST_BIG"),
        reason="set DASK_ML_TPU_TEST_BIG=1 for the >=10 GB tier",
    )
    def test_12gb_stream_bounded_rss(self, tmp_path):
        """The out-of-core scale: >=10 GB on disk, RSS bounded.
        Run manually (DASK_ML_TPU_TEST_BIG=1) — result recorded in
        docs/design.md §6."""
        p = tmp_path / "huge.csv"
        rows = _write_big_csv(p, 12.0)
        try:
            res = _stream_in_child(str(p))
        finally:
            p.unlink()
        assert res["rows"] == rows
        assert res["peak_mb"] - res["first_peak_mb"] < 500, res
        assert res["peak_mb"] < 1500, res
