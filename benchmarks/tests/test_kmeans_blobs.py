"""Tests of what PR 28 adds to the benchmark (``python -m pytest
benchmarks/tests/test_kmeans_blobs.py -q``; CPU, small sizes; they prove
arithmetic and verdicts, never a time):

- ``counts/kmeans_round.py`` against hand-worked numbers;
- the generator: equal blobs, every seed the same table mirrored;
- the cell ``kmeans-blobs.fit-1chip`` rehearsed small: the result line's
  keys, ``correct``, the metrics a CPU trace can give;
- each of the six new readers by hand, on made-up spans and a made-up
  trace, and ``None`` where there is nothing to read;
- the bfloat16 control and every planted fault come out not correct.

``test_benchmark.py`` keys its CPU sizes by configuration
(``SMALL_ROWS``, ``FAULT_ROWS``) and knows this one not; the sizes of this
cell's rehearsals are here.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run as harness  # noqa: E402

import control_kmeans  # noqa: E402

from dask_ml_tpu import obs  # noqa: E402

CELL = "kmeans-blobs.fit-1chip"
ROWS = 200_000
CPU_PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
READERS = ("init.wall_ms", "lloyd.wall_ms", "init.rounds", "init.candidates",
           "init.roof_pct", "lloyd.hbm_roof_pct")


def small_cell():
    cell = harness.load_cell(CELL)
    cell["config_data"]["generator_params"]["block_rows"] = 25_000
    return cell


def small_run(seed=5, trace=False, estimator=None, tmp=None):
    import jax

    return harness.run_cell(
        small_cell(), seed, 0.2, trace, devices=jax.devices()[:1],
        peaks=CPU_PEAKS, rows_per_chip=ROWS, estimator=estimator,
        trace_dir=None if tmp is None else str(tmp))


# ---- counts and generator ---------------------------------------------------

def test_counts_by_hand():
    counts = harness.load_module("counts", "kmeans_round")
    args = {"n_clusters": 8, "oversampling_factor": 2}
    assert counts.per_round(25_000_000, 50, args) == {
        "bytes": 5_000_000_000, "flops": 40_000_000_000,
        "init_bytes": 5_000_000_000, "init_flops": 160_000_000_000}
    # k = 1: cap is at least 8 slots
    assert counts.per_round(10, 3, {"n_clusters": 1}) == {
        "bytes": 120, "flops": 120, "init_bytes": 120, "init_flops": 480}


def test_generator_makes_equal_blobs_and_seeds_mirror_them():
    import jax
    import numpy as np

    cfg = harness.load_cell(CELL)["config_data"]
    gen = harness.load_module("generators", cfg["generator"])
    params = dict(cfg["generator_params"], block_rows=800)
    made = [gen.make(harness.seed_key(jax, seed), 4 * 800, params,
                     harness.row_sharding(jax.devices()[:1]))
            for seed in (5, 2**31 + 77)]
    a, b = (np.asarray(m["X"]) for m in made)
    assert a.shape == (3200, 50) and a.dtype == np.float32
    assert made[0]["y"] is None
    signs = np.sign(a[0] * b[0])
    assert set(np.unique(signs)) == {-1.0, 1.0}  # the seeds differ
    assert np.array_equal(a * signs[None, :], b)
    ca, cb = (np.asarray(m["truth"]["centers"]) for m in made)
    assert ca.shape == (8, 50) and np.array_equal(ca * signs, cb)
    assert np.abs(ca).max() <= 10.0
    # row i belongs to blob i % 8: equal sizes, every blob in every block
    nearest = ((a[:, None, :] - ca[None, :, :]) ** 2).sum(-1).argmin(1)
    assert np.array_equal(nearest, np.arange(3200) % 8)
    spread = np.sqrt(((a - ca[nearest]) ** 2).mean())
    assert spread == pytest.approx(cfg["generator_params"]["cluster_std"],
                                   rel=0.02)
    with pytest.raises(ValueError):
        gen.make(harness.seed_key(jax, 1), 3001, params,
                 harness.row_sharding(jax.devices()[:1]))


# ---- the cell, rehearsed small on the CPU -----------------------------------

def test_cell_rehearsal_prints_the_contracts_keys(tmp_path, capsys):
    cell = harness.load_cell(CELL)
    line = small_run()
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks" and set(line["checks"]) == set(
        cell["config_data"]["limits"])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("correct: True")
    json.dumps(line)  # one JSON object

    was = obs.enabled()
    obs.disable()  # the profiler session alone arms the program's spans
    obs.clear_spans()
    try:
        traced = small_run(seed=2**31 + 6, trace=True, tmp=tmp_path / "tr")
        roots = [r for r in obs.span_records()
                 if r.name == "kmeans.fit" and r.parent_id is None]
    finally:
        if was:
            obs.enable()
    assert traced["correct"] is True
    allowed = {m["name"] for m in cell["per_layer"]}
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(m) <= allowed
    assert not set(m) & {"fit.classes_ms", "solve.passes", "solve.wall_ms"}
    # a CPU trace has no device lane: the two roofline shares and
    # solve.program_ms have nothing to read; the four from spans do
    assert {"ingest_s", "solve.rounds", "window.compiles", "fit.max_ms",
            "fit.mfu_pct", "fit.hbm_roof_pct", "init.wall_ms",
            "lloyd.wall_ms", "init.rounds", "init.candidates"} <= set(m)
    assert "init.roof_pct" not in m and "lloyd.hbm_roof_pct" not in m
    assert m["window.compiles"] == 0
    assert len(roots) == traced["extra"]["fits"] >= 1
    assert m["solve.rounds"] >= 1 and m["init.rounds"] >= 2
    assert 1 < m["init.candidates"] <= 1 + 64 * m["init.rounds"]
    mean_fit_ms = 1e3 * sum(r.t1 - r.t0 for r in roots) / len(roots)
    assert m["init.wall_ms"] + m["lloyd.wall_ms"] < mean_fit_ms
    # every seed the same table mirrored: the same checks to the digit
    for name, (value, _limit) in line["checks"].items():
        assert value == pytest.approx(traced["checks"][name][0], rel=1e-6)


# ---- the six readers, by hand -----------------------------------------------

def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def made_up_ctx(fits):
    return {"trace": {"fits": fits},
            "cell": {"config_data": {"init_modules": ["jit_first", "jit_rounds"],
                                     "lloyd_modules": ["jit_lloyd"]}},
            "least": {"bytes": 10**9, "flops": 4 * 10**9,
                      "init_bytes": 10**9, "init_flops": 4 * 10**10},
            "peaks": {"hbm_bytes_per_s": 1e10, "flops_per_s": 1e11}}


@pytest.fixture
def recording():
    """The rings empty and recording on (as a profiler session would have
    it), put back as found."""
    was = obs.enabled()
    obs.clear_spans()
    obs.enable()
    yield
    obs.clear_spans()
    if not was:
        obs.disable()


_ids = iter(range(2 * 10**6, 10**7))


def made_up_fit(rounds, iters, candidates=300):
    """One completed ``kmeans.fit`` root of 1 s, later than the last: the
    init 0.0-0.6 s, Lloyd 0.6-0.9 s, the assignment 0.9-0.95 s."""
    root = next(_ids)
    t = float(root)
    for name, lo, hi, attrs in (
            ("kmeans.init", 0.0, 0.6,
             {} if rounds is None else
             {"rounds": rounds, "candidates": candidates, "cap": 64}),
            ("kmeans.lloyd", 0.6, 0.9, {} if iters is None else {"iters": iters}),
            ("kmeans.assign", 0.9, 0.95, {})):
        obs.spans._emit(obs.SpanRecord(
            "span", next(_ids), root, name, t + lo, t + hi, "MainThread",
            attrs))
    obs.spans._emit(obs.SpanRecord(
        "span", root, None, "kmeans.fit", t, t + 1.0, "MainThread", {}))


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_without_a_trace_or_spans(name, recording):
    made_up_fit(rounds=20, iters=2)
    assert read(name, dict(made_up_ctx([]), trace=None)) is None
    obs.clear_spans()  # a trace, but a program that opened no span
    fits = [{"modules": {"jit_rounds": 0.5, "jit_lloyd": 0.1}}]
    assert read(name, made_up_ctx(fits)) is None


def test_readers_by_hand(recording):
    made_up_fit(rounds=5, iters=9)  # an older fit, outside the trace
    made_up_fit(rounds=20, iters=2, candidates=300)
    made_up_fit(rounds=30, iters=4, candidates=500)
    fits = [{"modules": {"jit_first": 1.0, "jit_rounds": 9.0, "jit_lloyd": 1.0,
                         "jit_other": 3.0}},
            {"modules": {"jit_rounds": 20.0, "jit_lloyd": 1.0}}]
    ctx = made_up_ctx(fits)
    assert read("init.wall_ms", ctx) == pytest.approx(600.0)
    assert read("lloyd.wall_ms", ctx) == pytest.approx(300.0)
    assert read("init.rounds", ctx) == 25.0
    assert read("init.candidates", ctx) == 400.0
    # an init round is the larger of 1e9 B / 1e10 B/s = 0.1 s and
    # 4e10 flop / 1e11 flop/s = 0.4 s: 20 rounds in 10 s of the init's
    # two modules, 30 in 20 s; the mean of the two shares
    assert read("init.roof_pct", ctx) == pytest.approx(
        100 * (20 * 0.4 / 10.0 + 30 * 0.4 / 20.0) / 2)
    # a Lloyd round reads X once, 0.1 s: 2 in 1 s, 4 in 1 s
    assert read("lloyd.hbm_roof_pct", ctx) == pytest.approx(
        100 * (2 * 0.1 / 1.0 + 4 * 0.1 / 1.0) / 2)
    # where the bytes bind the init round (no flops to speak of)
    ctx["least"]["init_flops"] = 10**9
    assert read("init.roof_pct", ctx) == pytest.approx(
        100 * (20 * 0.1 / 10.0 + 30 * 0.1 / 20.0) / 2)
    # no Lloyd module ran in one of the fits: no share
    ctx = made_up_ctx([fits[0], {"modules": {"jit_rounds": 1.0}}])
    assert read("lloyd.hbm_roof_pct", ctx) is None
    assert read("init.roof_pct", ctx) is not None
    # a count function without the init's keys (another configuration's)
    del ctx["least"]["init_bytes"]
    assert read("init.roof_pct", ctx) is None


def test_counts_are_missing_where_the_program_counts_nothing(recording):
    made_up_fit(rounds=None, iters=None)  # another init: a span, no counts
    ctx = made_up_ctx([{"modules": {"jit_rounds": 1.0, "jit_lloyd": 1.0}}])
    assert read("init.wall_ms", ctx) == pytest.approx(600.0)
    for name in ("init.rounds", "init.candidates", "init.roof_pct",
                 "lloyd.hbm_roof_pct"):
        assert read(name, ctx) is None


# ---- the control and the planted faults come out as not correct --------------

def test_control_and_faults_are_not_correct():
    """``control_kmeans.py``'s readings, through ``run_cell`` as on the
    chip: the program passes; the reference in bfloat16 in its place, a
    fit that returns its start, half the rows left out and one altered
    number do not, each by the number reckoned to catch it."""
    import jax

    cell = small_cell()
    limits = cell["config_data"]["limits"]
    out = control_kmeans.readings(
        cell, 7, devices=jax.devices()[:1], peaks=CPU_PEAKS,
        faults=list(control_kmeans.FAULTS), rows_per_chip=ROWS)
    assert out["program"]["passes"] is True
    assert all(out["program"][k] <= v for k, v in limits.items())
    caught_by = {"control.bfloat16": "inertia_gap",
                 "fault.state_unchanged": "centre_gap",
                 "fault.half_batch": "label_mismatch",
                 "fault.answer_altered": "centre_gap"}
    for reading, number in caught_by.items():
        assert out[reading]["passes"] is False, reading
        assert out[reading][number] > 3 * limits[number], (reading, number)
    assert out["fault.half_batch"]["inertia_gap"] == pytest.approx(0.5, abs=0.01)


def test_planted_rejects_an_unknown_fault():
    with pytest.raises(ValueError):
        control_kmeans.planted(object, "no_such_fault")


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH, "references", "kmeans_lloyd.py")).read()
    assert "import dask_ml_tpu" not in source
    assert "from dask_ml_tpu" not in source
