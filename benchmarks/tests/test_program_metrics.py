"""Tests of the seven per-layer metrics that read the program's own spans
and counters (``python -m pytest benchmarks/tests -q``; CPU, small sizes;
they prove the readers' arithmetic, never a time):

- each reader returns ``None`` without a trace and without spans;
- ``solve.hbm_roof_pct`` and ``fit.self_ms`` by hand, from a made-up
  ``ctx`` and made-up spans;
- the CPU rehearsal of both cells under ``--trace 1`` prints the six that
  need no device lane, and the four durations add up to ``glm.fit``.
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run as harness  # noqa: E402

from dask_ml_tpu import obs  # noqa: E402

CELLS = [w["name"] for w in harness.load_json(ROOT, "BENCHMARK.json")["workloads"]]
SPAN_METRICS = ("fit.classes_ms", "fit.prepare_ms", "fit.self_ms",
                "solve.wall_ms")
COUNT_METRICS = ("solve.inner_iters", "solve.passes")
NEW = SPAN_METRICS + COUNT_METRICS + ("solve.hbm_roof_pct",)


def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def made_up_ctx(fits):
    return {"trace": {"fits": fits},
            "cell": {"config_data": {"solve_modules": ["jit_solve"]}},
            "least": {"bytes": 10**9},
            "peaks": {"hbm_bytes_per_s": 1e10}}


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


_made_up_ids = iter(range(10**6, 10**7))


def made_up_fit(passes, children=(("glm.classes", 0.0, 0.3),
                                   ("glm.prepare", 0.3, 0.4),
                                   ("glm.solve", 0.4, 0.9))):
    """One completed ``glm.fit`` root of 1 s, later than the last, with
    children at the given offsets, as the program's spans would have
    left them in the rings."""
    root = next(_made_up_ids)
    t = float(root)
    for name, lo, hi in children:
        attrs = ({"passes": passes, "inner_iters": 7}
                 if passes and name == "glm.solve" else {})
        obs.spans._emit(obs.SpanRecord(
            "span", next(_made_up_ids), root, name, t + lo, t + hi,
            "MainThread", attrs))
    obs.spans._emit(obs.SpanRecord(
        "span", root, None, "glm.fit", t, t + 1.0, "MainThread", {}))


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_nothing_without_a_trace_or_spans(name, recording):
    made_up_fit(passes=20)
    assert read(name, dict(made_up_ctx([]), trace=None)) is None
    obs.clear_spans()  # a trace, but a program that opened no span
    fits = [{"wall_s": 1.0, "busy_s": 0.9, "modules": {"jit_solve": 0.5}}]
    assert read(name, made_up_ctx(fits)) is None


def test_counts_are_missing_where_the_solver_counts_nothing(recording):
    made_up_fit(passes=None)
    ctx = made_up_ctx([{"modules": {"jit_solve": 0.5}}])
    assert read("solve.wall_ms", ctx) == pytest.approx(500.0)
    for name in COUNT_METRICS + ("solve.hbm_roof_pct",):
        assert read(name, ctx) is None


def test_hbm_roof_and_the_durations_by_hand(recording):
    made_up_fit(passes=5)   # an older fit, outside the trace
    made_up_fit(passes=20)
    made_up_fit(passes=30)
    fits = [{"modules": {"jit_solve": 4.0, "jit_other": 9.0}},
            {"modules": {"jit_solve": 5.0}}]
    ctx = made_up_ctx(fits)
    # a read of X is 1e9 B / 1e10 B/s = 0.1 s: 20 of them in 4 s of the
    # solve's device time, 30 in 5 s; the mean of the two shares
    assert read("solve.hbm_roof_pct", ctx) == pytest.approx(
        100 * (20 * 0.1 / 4.0 + 30 * 0.1 / 5.0) / 2)
    assert read("solve.passes", ctx) == 25.0
    assert read("solve.inner_iters", ctx) == 7.0
    assert read("fit.classes_ms", ctx) == pytest.approx(300.0)
    assert read("fit.prepare_ms", ctx) == pytest.approx(100.0)
    assert read("solve.wall_ms", ctx) == pytest.approx(500.0)
    assert read("fit.self_ms", ctx) == pytest.approx(100.0)
    # no solve module ran in one of the fits: no share
    ctx = made_up_ctx([fits[0], {"modules": {"jit_other": 1.0}}])
    assert read("solve.hbm_roof_pct", ctx) is None


def test_self_time_takes_the_union_of_children_out_not_their_sum(recording):
    # two children overlap in [0.3, 0.5]: covered 0.1..0.7 = 0.6 s, while
    # their durations sum to 0.8 s
    made_up_fit(passes=None, children=(("a", 0.1, 0.5), ("b", 0.3, 0.7)))
    ctx = made_up_ctx([{"modules": {}}])
    assert read("fit.self_ms", ctx) == pytest.approx(400.0)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_the_program_metrics(workload, tmp_path):
    """Both cells, small, on the CPU under ``--trace 1``, with recording
    NOT enabled: the profiler session alone arms the program's spans."""
    import jax

    was = obs.enabled()
    obs.disable()
    obs.clear_spans()
    try:
        cell = harness.load_cell(workload)
        line = harness.run_cell(
            cell, 7, 0.2, True, devices=jax.devices()[:1],
            peaks={"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}},
            rows_per_chip=100_000, trace_dir=str(tmp_path / "tr"))
        roots = [r for r in obs.span_records()
                 if r.name == "glm.fit" and r.parent_id is None]
    finally:
        if was:
            obs.enable()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the CPU trace has no device lane, so no module seconds: the
    # roofline share (like solve.program_ms) has nothing to read here
    assert set(SPAN_METRICS + COUNT_METRICS) <= set(m)
    assert "solve.hbm_roof_pct" not in m and "solve.program_ms" not in m
    assert m["window.compiles"] == 0
    # the session ends with the window: as many roots as traced fits,
    # none from the warm-up fit before it
    assert len(roots) == line["extra"]["fits"] >= 1
    mean_fit_ms = 1e3 * sum(r.t1 - r.t0 for r in roots) / len(roots)
    assert sum(m[name] for name in SPAN_METRICS) == pytest.approx(
        mean_fit_ms, rel=1e-9)
    assert m["solve.passes"] >= m["solve.rounds"] + m["solve.inner_iters"]
    assert m["solve.passes"] == int(m["solve.passes"])  # the path repeats
