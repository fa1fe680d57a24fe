"""Tests of the benchmark itself (``python -m pytest benchmarks/tests -q``;
not part of the repo's tier-1 suite).  They run on the CPU at small
sizes: they prove the harness's arithmetic and its verdicts, never a time.

- the trace reduction, on hand-made events and on a small recorded TPU
  trace (``data/small.xplane.pb``: three tiny ADMM fits on one v5e);
- the count functions against hand-worked numbers;
- the contract's rules for names, units and keys over ``BENCHMARK.json``;
- a rehearsal of every cell: its files resolve by name and the result
  line has the contract's keys;
- the controls (the reference in the next precision down) come out as not
  correct, and so does a run with the timed path broken underneath.
"""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run as harness  # noqa: E402
import trace as reduction  # noqa: E402

BENCHMARK = harness.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
#: rows for the CPU runs: large enough that a sound fit passes the limits
#: that were set at the cells' own sizes on the chip
SMALL_ROWS = {"admm-higgs": 1_000_000, "admm-higgs-probegrid": 1_000_000}
#: the planted faults fail at any size; a smaller table keeps them quick
FAULT_ROWS = {"admm-higgs": 100_000, "admm-higgs-probegrid": 100_000}
CPU_PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


# ---- trace reduction ------------------------------------------------------

def test_union_gaps_and_clipping_by_hand():
    merged = reduction.union([(0, 10), (5, 20), (30, 40), (40, 45), (60, 61)])
    assert merged == [[0, 20], [30, 45], [60, 61]]
    assert reduction.clipped_length(merged, 10, 35) == 10 + 5
    assert reduction.gaps(merged, 10, 70) == [(20, 30), (45, 60), (61, 70)]
    assert reduction.gaps([], 3, 9) == [(3, 9)]


def test_self_seconds_takes_nested_ops_out():
    own = reduction.self_seconds([
        ("while", 0, 100), ("a", 10, 30), ("b", 40, 60),
        ("outer", 200, 300), ("mid", 200, 250), ("leaf", 210, 220)])
    ns = {k: round(v * 1e9) for k, v in own.items()}
    assert ns == {"while": 60, "a": 20, "b": 20, "outer": 50, "mid": 40,
                  "leaf": 10}


def test_reduce_on_hand_made_events():
    s = 1_000_000_000  # one second in ns
    devices = {0: {
        "XLA Ops": [("%w = f32[4]{0} while(x)", 1 * s, 3 * s),
                    ("%f = f32[4]{0} fusion(x)", 1 * s, 2 * s),
                    ("%g = f32[4]{0} fusion(x)", 5 * s, 6 * s)],
        "XLA Modules": [("jit_solve(11)", 1 * s, 3 * s),
                        ("jit_other(12)", 5 * s, 6 * s)]}}
    host = [("bench.fit", 0, 4 * s), ("bench.fetch", 3 * s + s // 2, 4 * s),
            ("bench.between", 4 * s, 5 * s), ("bench.fit", 5 * s, 8 * s),
            ("unrelated", 0, 9 * s)]
    out = reduction.reduce(
        devices, host, window=("bench.fit", "bench.fetch", "bench.between"))
    assert out["window_s"] == 8.0
    assert out["busy_by_device"] == [3.0] and out["busy_s"] == 3.0
    assert [f["wall_s"] for f in out["fits"]] == [4.0, 3.0]
    assert [f["busy_s"] for f in out["fits"]] == [2.0, 1.0]
    assert out["fits"][0]["modules"] == {"jit_solve": 2.0}
    assert out["fits"][1]["modules"] == {"jit_other": 1.0}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == {"jit_solve/%w f32[4] while": 1.0,
                   "jit_solve/%f f32[4] fusion": 1.0,
                   "jit_other/%g f32[4] fusion": 1.0}
    # idle: [0,1] and [3,3.5] in the fit, [3.5,4] in the fetch, [4,5]
    # between, [6,8] in the second fit; the gap [3,5] is cut at 3.5 and 4
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == {"bench.fit": 1.0 + 0.5 + 2.0, "bench.fetch": 0.5,
                    "bench.between": 1.0}
    # the per-layer readers on that summary
    ctx = {"trace": out, "counters": {"fit_walls_s": [4.0, 3.0], "rounds": 2},
           "least": {"bytes": 10**9, "flops": 10**9},
           "peaks": {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e10}}
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)  # noqa: E731
    ctx["cell"] = {"config_data": {"solve_modules": ["jit_solve"]}}
    assert read("fit.host_ms") == pytest.approx(1e3 * (2.0 + 2.0) / 2)
    assert read("solve.program_ms") == pytest.approx(1e3 * (2.0 + 0.0) / 2)
    assert read("fit.nonsolve_ms") == pytest.approx(1e3 * (0.0 + 1.0) / 2)
    ctx["cell"] = {"config_data": {"solve_modules": ["jit_renamed"]}}
    assert read("solve.program_ms") is None
    assert read("device.idle_pct") == pytest.approx(100 * 5.0 / 8.0)
    assert read("fit.max_ms") == 4000.0
    assert read("fit.hbm_roof_pct") == pytest.approx(100 * 2.0 / 3.5)
    assert read("fit.mfu_pct") == pytest.approx(100 * 0.2 / 3.5)


def test_readers_return_nothing_without_a_trace():
    ctx = {"trace": None, "counters": {"fit_walls_s": [], "rounds": None},
           "cell": {"config_data": {}}}
    for name in ("fit.host_ms", "solve.program_ms", "device.idle_pct",
                 "fit.nonsolve_ms",
                 "fit.max_ms", "fit.hbm_roof_pct", "fit.mfu_pct"):
        assert harness.load_module("layer_metrics", name).read(ctx) is None


def test_reduce_the_recorded_tpu_trace():
    """Three tiny ADMM fits on one v5e (recorded in PR 25)."""
    out = reduction.reduce_dir(
        os.path.join(HERE, "data"),
        window=("bench.fit", "bench.fetch", "bench.between"))
    assert len(out["fits"]) == 3 and len(out["busy_by_device"]) == 1
    assert 0 < out["busy_s"] < out["window_s"]
    for fit in out["fits"]:
        assert 0 < fit["busy_s"] <= fit["wall_s"]
        assert "jit__admm_run" in fit["modules"]
        # no module can run longer than the device was busy in the fit
        assert max(fit["modules"].values()) <= fit["busy_s"] + 1e-9
    ops = out["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and ops == sorted(ops, key=lambda kv: -kv[1])
    # operations less what is nested in them cannot sum past the busy time
    assert sum(v for _, v in ops) <= out["busy_s"] + 1e-9
    gaps = dict(out["breakdown"]["idle_gaps"])
    # "outside": the host between two of the benchmark's own spans
    assert set(gaps) <= {"bench.fit", "bench.fetch", "bench.between", "outside"}
    assert gaps["bench.fit"] == max(gaps.values())
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)


# ---- count functions --------------------------------------------------------

def test_counts_by_hand():
    logistic = harness.load_module("counts", "logistic_pass")
    assert logistic.per_round(31_250_000, 28, {}) == {
        "bytes": 3_500_000_000, "flops": 3_500_000_000}
    assert logistic.per_round(10, 3, {"C": 1.0}) == {"bytes": 120, "flops": 120}


# ---- the contract's rules over BENCHMARK.json -----------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_to_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p) for p in b["paths"])
    assert len(b["command"]) <= 32 and all(one_line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    under = tuple(p.rstrip("/") + "/" for p in b["paths"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith(under) and PATH.match(c["file"])
        data = harness.load_json(ROOT, c["file"])
        assert len(c["reduced"]) <= 16 and data["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in data and key in data["reduced_why"]
            assert not re.search(r"(_dim|_rank)$|hidden|width|features", key)
        assert data["features"] == data["published"]["features"]  # no width cut
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    configs = {c["name"] for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["moves"] in e2e and one_line(m["layer"])
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"} and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= set(CELLS)
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names) and len(set(CELLS)) == len(CELLS)
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and one_line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    assert configs == {w["config"] for w in b["workloads"]}
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    for root, _dirs, files in os.walk(BENCH):
        if "__pycache__" in root or "/." in root[len(BENCH):]:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)


# ---- every cell, rehearsed small on the CPU -----------------------------------------

def small_run(workload, seed=5, trace=False, estimator=None, tmp=None):
    import jax

    cell = harness.load_cell(workload)
    rows = SMALL_ROWS if estimator is None else FAULT_ROWS
    chips = int(cell["chips"])  # virtual CPU devices (conftest.py)
    return cell, harness.run_cell(
        cell, seed, 0.2, trace, devices=jax.devices()[:chips], peaks=CPU_PEAKS,
        rows_per_chip=rows[cell["config"]] // chips, estimator=estimator,
        trace_dir=None if tmp is None else str(tmp))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal_prints_the_contracts_keys(workload, tmp_path, capsys):
    cell, line = small_run(workload)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks" and set(line["checks"]) == set(
        cell["config_data"]["limits"])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("correct: True")
    assert [ln.split(":")[0] for ln in err[-1 - len(line["checks"]):-1]] == [
        "check " + k for k in line["checks"]]
    json.dumps(line)  # one JSON object
    # the traced form: per-layer metrics, each from its own reader
    _, traced = small_run(workload, seed=6, trace=True, tmp=tmp_path / "tr")
    allowed = {m["name"] for m in cell["per_layer"]}
    assert set(traced["metrics"]) <= allowed
    assert {"ingest_s", "solve.rounds", "window.compiles", "fit.max_ms",
            "fit.mfu_pct", "fit.hbm_roof_pct"} <= set(traced["metrics"])
    assert traced["metrics"]["window.compiles"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_seeds_mirror_one_table_and_do_the_same_work():
    """The run seed flips the signs of feature columns and nothing else:
    same labels, |X| equal, and the fit mirrors bit for bit, so every
    seed reads the same checks and the coefficients differ by the signs."""
    import jax
    import numpy as np

    cell = harness.load_cell(CELLS[0])
    cfg = cell["config_data"]
    generator = harness.load_module("generators", cfg["generator"])
    made = [generator.make(harness.seed_key(jax, seed), 4 * 1000,
                           dict(cfg["generator_params"], block_rows=1000),
                           harness.row_sharding(jax.devices()[:1]))
            for seed in (5, 2**31 + 77)]
    a, b = (np.asarray(m["X"]) for m in made)
    signs = np.sign(a[0] * b[0])
    assert set(np.unique(signs)) == {-1.0, 1.0}  # the seeds differ
    assert np.array_equal(a * signs[None, :], b)
    assert np.array_equal(np.asarray(made[0]["y"]), np.asarray(made[1]["y"]))
    assert np.array_equal(np.asarray(made[0]["truth"]["w"]) * signs,
                          np.asarray(made[1]["truth"]["w"]))
    lines = [small_run(CELLS[0], seed=seed)[1] for seed in (5, 2**31 + 77)]
    assert lines[0]["correct"] and lines[1]["correct"]
    for name, (value, _limit) in lines[0]["checks"].items():
        assert value == pytest.approx(lines[1]["checks"][name][0], rel=1e-6)


def test_harness_refuses_a_machine_without_a_tpu():
    """Here jax is held to the CPU: the command must exit non-zero and
    print no result."""
    import subprocess

    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert done.returncode != 0 and done.stdout.strip() == ""


# ---- controls and planted faults come out as not correct ---------------------------------

@pytest.mark.parametrize("workload", CELLS)
def test_control_in_the_next_precision_down_is_not_correct(workload):
    """``control.py``'s readings, through ``run_cell`` as on the chip: the
    program passes, the reference in bfloat16 in its place does not, and
    a fault read the same way fails too."""
    import jax

    import control

    cell = harness.load_cell(workload)
    chips = int(cell["chips"])
    out = control.readings(
        cell, 7, devices=jax.devices()[:chips], peaks=CPU_PEAKS,
        faults=["half_batch"],
        rows_per_chip=SMALL_ROWS[cell["config"]] // chips)
    assert out["program"]["passes"] is True
    limits = cell["config_data"]["limits"]
    assert all(out["program"][k] <= v for k, v in limits.items())
    assert out["control.bfloat16"]["passes"] is False
    assert any(out["control.bfloat16"][k] > v for k, v in limits.items())
    assert out["fault.half_batch"]["passes"] is False


def test_control_set_key_changes_one_key():
    import control

    cfg = {"estimator_args": {"solver_kwargs": {"line_search": "probe_grid"}},
           "generator_params": {"table_seed": 0}}
    control.set_key(cfg, 'estimator_args.solver_kwargs.line_search="backtrack"')
    control.set_key(cfg, "generator_params.table_seed=3")
    assert cfg == {"estimator_args": {"solver_kwargs": {"line_search": "backtrack"}},
                   "generator_params": {"table_seed": 3}}


def broken(workload, fault):
    """The cell's estimator with one fault planted under the timed path."""
    import control

    cfg = harness.load_cell(workload)["config_data"]
    return control.planted(harness.import_attr(cfg["estimator"]), fault)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(workload, fault):
    _, line = small_run(workload, estimator=broken(workload, fault))
    assert line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    assert any(v > limit for v, limit in line["checks"].values())


def test_a_fit_that_raises_is_counted_and_not_correct():
    workload = CELLS[0]
    real = harness.import_attr(harness.load_cell(workload)["config_data"]["estimator"])
    calls = {"n": 0}

    class Raises(real):
        def fit(self, X, y=None):
            calls["n"] += 1
            if calls["n"] > 1:  # the warm-up passes, the window's fit fails
                raise RuntimeError("planted")
            return super().fit(X, y)

    _, line = small_run(workload, estimator=Raises)
    assert line["failed"] == 1 and line["attempted"] == 1
    assert line["correct"] is False and "fit_s" not in line["metrics"]
