"""The incremental SGD cell's benchmark files at a CPU size: the plain
reference (``references/sgd_blocks.py``) against the program, the
bfloat16 control and each planted fault (``control_sgd.py``) failing
``correct``, the count function and the readers.  Arithmetic and
verdicts only, never a time.

    python -m pytest benchmarks/tests/test_incremental_sgd.py -q
"""

import os
import sys

import numpy as np
import pytest

import jax

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import control_sgd  # noqa: E402
import run as harness  # noqa: E402

CELL = "incremental-sgd-higgs.fit-1chip"
ROWS, CHUNK = 24_000, 2_000  # twelve blocks a pass
CPU_PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _small_cell():
    cell = harness.load_cell(CELL)
    cfg = cell["config_data"]
    cfg["generator_params"]["block_rows"] = 12_000
    cfg["estimator_args"]["chunk_size"] = CHUNK
    return cell


def _run(estimator=None, trace=False, trace_dir=None):
    return harness.run_cell(
        _small_cell(), 2**31 + 5, 0.2 if trace else 0.0, trace,
        devices=jax.devices()[:1], peaks=CPU_PEAKS, rows_per_chip=ROWS,
        estimator=estimator, trace_dir=trace_dir)


def test_cell_rehearsed_small(tmp_path):
    """Traced, at 24,000 rows: ``correct``, 40 passes of 12 blocks, no
    table byte on the host, no wait after the first pass of the first
    fit, no compile in the window."""
    result = _run(trace=True, trace_dir=str(tmp_path))
    assert result["correct"] and result["failed"] == 0
    assert set(result["checks"]) == {"coef_gap", "steps_gap"}
    assert result["checks"]["steps_gap"][0] == 0.0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["solve.rounds"] == 40 and metrics["window.compiles"] == 0
    assert metrics["pass.blocks"] == 12
    assert metrics["pass.table_host_mb"] == 0 and metrics["pass.host_waits"] == 0
    assert metrics["pass.wall_ms"] > 0


#: the numbers that tell each reading that is not the program's
TOLD_BY = {
    "control": {"coef_gap"},
    "half_batch": {"coef_gap", "steps_gap"},
    "pass_dropped": {"coef_gap", "steps_gap"},
    "state_reset": {"coef_gap", "steps_gap"},
    "order_ignored": {"coef_gap"},
}


@pytest.mark.parametrize("fault", list(TOLD_BY))
def test_control_and_planted_faults_read_not_correct(fault):
    """The bfloat16 control and each guarantee of the passes turned off,
    under the timed path: every one fails the limits that read it."""
    cfg = _small_cell()["config_data"]
    reference = harness.load_module("references", cfg["reference"])
    estimator = (reference.control_estimator("bfloat16") if fault == "control"
                 else control_sgd.planted(harness.import_attr(cfg["estimator"]),
                                          fault))
    result = _run(estimator)
    assert not result["correct"]
    assert {name for name, (value, limit) in result["checks"].items()
            if value > limit} == TOLD_BY[fault]


def test_reference_follows_its_blocks():
    """The order is ``RandomState(random_state)``'s shuffle of the block
    list; the last block is short; the step count is blocks x passes."""
    cfg = _small_cell()["config_data"]
    reference = harness.load_module("references", cfg["reference"])
    args = dict(cfg["estimator_args"], chunk_size=300)
    spans = reference.blocks(1003, args)
    assert sorted(spans) == [(lo, min(lo + 300, 1003))
                             for lo in range(0, 1003, 300)]
    expected = [(lo, min(lo + 300, 1003)) for lo in range(0, 1003, 300)]
    np.random.RandomState(0).shuffle(expected)
    assert spans == expected
    assert reference.blocks(1003, dict(args, shuffle_blocks=False)) == sorted(
        spans)
    with pytest.raises(ValueError, match="states loss"):
        reference.build({"X": None, "y": None},
                        dict(args, estimator={"loss": "hinge"}))


def test_counts_and_readers_by_hand():
    """A pass's least work, and the device-time readers on a made-up
    trace: 40 passes of 4 blocks in 0.95 s of ``jit__sgd_pass``."""
    cfg = _small_cell()["config_data"]
    counts = harness.load_module("counts", cfg["counts"])
    least = counts.per_round(62_500_000, 28, dict(cfg["estimator_args"],
                                                  chunk_size=1_048_576))
    assert least == {"bytes": 62_500_000 * 120,
                     "flops": 4 * 62_500_000 * 28, "steps": 60}
    from dask_ml_tpu import obs

    was = obs.enabled()
    obs.enable()
    try:  # 40 passes of 4 blocks: the spans the readers take
        harness.import_attr(cfg["estimator"])(**dict(
            cfg["estimator_args"], chunk_size=250)).fit(*_table(1000))
    finally:
        if not was:
            obs.disable()
    ctx = {"trace": {"fits": [{"modules": {"jit__sgd_pass": 0.95}}]},
           "cell": {"config_data": cfg}, "least": least,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    step = harness.load_module("layer_metrics", "sgd.step_us").read(ctx)
    roof = harness.load_module("layer_metrics", "sgd.hbm_roof_pct").read(ctx)
    assert step == pytest.approx(0.95e6 / (40 * 4))
    assert roof == pytest.approx(100 * 40 * least["bytes"] / 819e9 / 0.95)
    for name in ("sgd.step_us", "sgd.hbm_roof_pct", "pass.blocks"):
        reader = harness.load_module("layer_metrics", name)
        assert reader.read(dict(ctx, trace=None)) is None
    assert harness.load_module("layer_metrics", "sgd.step_us").read(
        dict(ctx, trace={"fits": [{"modules": {}}]})) is None


def _table(rows):
    from dask_ml_tpu.core import shard_rows

    rng = np.random.RandomState(0)
    X = rng.normal(size=(rows, 28)).astype(np.float32)
    y = (X @ rng.normal(size=28) + rng.logistic(size=rows) > 0)
    return shard_rows(X), shard_rows(y.astype(np.float32))


def test_a_pass_that_streams_the_table_stops_the_run(monkeypatch):
    """Where a pass is not one dispatch of the pass program (the host
    stream, which reads the table back: a parent commit), the factory's
    first pass raises, and the run ends at once instead of minutes a
    fit."""
    from dask_ml_tpu.linear_model import _sgd_pass

    cfg = _small_cell()["config_data"]
    make = harness.import_attr(cfg["estimator"])
    args = dict(cfg["estimator_args"], chunk_size=250)
    make(**args).fit(*_table(1000))
    monkeypatch.setattr(_sgd_pass, "takes", lambda *a: False)
    with pytest.raises(RuntimeError, match="one dispatch of 'sgd.pass'"):
        make(**args).fit(*_table(1000))
