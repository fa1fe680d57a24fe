"""grafttrace tests (ISSUE 7 tentpole): the span/metrics/flight spine.

Covers the acceptance criteria: a depth-2 streamed SGD fit yields ONE
span tree with pipeline stage children + a retry event from an injected
``FaultPlan`` fault + registry histograms with p50/p99; tracing enabled
costs three records a block and no multiple of the wall; the JSONL log
round-trips through its schema; the flight recorder leaves a
post-mortem for step faults; and the legacy reporters keep their shapes
as registry views.
"""

import io as _io
import json
import math
import os
import threading
import time

import numpy as np
import pytest

from dask_ml_tpu import _partial, diagnostics, obs
from dask_ml_tpu.pipeline import PREFETCH_THREAD_NAME, stream_partial_fit


@pytest.fixture(autouse=True)
def _clean_obs():
    """Book isolation + restore the session-wide arming the conftest set
    up (tests below toggle enable/disable for the A/B)."""
    diagnostics.reset()
    yield
    diagnostics.reset()
    if not obs.enabled():
        obs.enable()


def _tree_names(node, out=None):
    """Flatten a span tree to [(name, thread)], spans and events."""
    if out is None:
        out = []
    out.append((node["name"], node["thread"]))
    for e in node.get("events", ()):
        out.append((e["name"], e["thread"]))
    for c in node.get("children", ()):
        _tree_names(c, out)
    return out


def _collect_nodes(node, out=None):
    """Flatten a span tree to its span-node dicts."""
    if out is None:
        out = []
    out.append(node)
    for c in node.get("children", ()):
        _collect_nodes(c, out)
    return out


class TestTracingLeftAsFound:
    """tests/conftest.py's autouse guard, driven on planted leaks: the
    test that disarms recording or leaves a span open fails itself, and
    the next test finds tracing as the suite armed it."""

    @pytest.mark.parametrize("plant, message", [
        ("disarmed", "left span recording disarmed"),
        ("span_open", "left a span open on its thread"),
        ("nothing", None),
    ])
    def test_guard_fails_the_leaking_test_and_repairs(self, plant,
                                                      message):
        from conftest import tracing_guard

        assert obs.enabled() and obs.current_span_id() is None
        guard = tracing_guard()
        next(guard)
        if plant == "disarmed":
            obs.disable()
        elif plant == "span_open":
            obs.span("left.open").__enter__()
        if message is None:
            with pytest.raises(StopIteration):
                next(guard)
        else:
            with pytest.raises(pytest.fail.Exception, match=message):
                next(guard)
        assert obs.enabled() and obs.current_span_id() is None


class TestMetricsRegistry:
    def test_counter_gauge_basics(self):
        reg = obs.registry()
        reg.counter("t.count").inc()
        reg.counter("t.count").inc(4)
        assert reg.counter("t.count").value == 5
        reg.gauge("t.depth").set(3.5)
        assert reg.gauge("t.depth").value == 3.5

    def test_histogram_quantiles_log_bucketed(self):
        reg = obs.registry()
        h = reg.histogram("t.lat_s")
        for v in range(1, 101):
            h.record(v / 1000.0)  # 1..100 ms
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["min"] == pytest.approx(0.001)
        assert snap["max"] == pytest.approx(0.100)
        # log buckets at 2^(1/4) growth: ~19% relative resolution
        assert snap["p50"] == pytest.approx(0.050, rel=0.25)
        assert snap["p99"] == pytest.approx(0.099, rel=0.25)
        assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]

    def test_histogram_single_sample_reports_sample(self):
        h = obs.registry().histogram("t.one")
        h.record(0.42)
        s = h.snapshot()
        assert s["p50"] == pytest.approx(0.42)
        assert s["p99"] == pytest.approx(0.42)

    def test_empty_histogram_nan_quantile(self):
        h = obs.registry().histogram("t.empty")
        assert math.isnan(h.quantile(0.5))
        assert h.snapshot() == {"count": 0}

    def test_tag_families(self):
        reg = obs.registry()
        reg.counter("t.retry", "ingest").inc(2)
        reg.counter("t.retry", "step").inc()
        assert reg.family("t.retry") == {"ingest": 2, "step": 1}
        snap = reg.snapshot()
        assert snap["counters"]["t.retry{ingest}"] == 2

    def test_histogram_concurrent_writers_lose_nothing(self):
        """Two threads recording into one ``pipeline.block_s`` family —
        the shape the serving lane's handler pool will drive — must not
        lose observations or corrupt the running sum: every record is
        one lock acquisition (obs/metrics.py), so count/sum/min/max
        stay exact under contention, including when both writers share
        ONE instrument and when they write sibling tags of a family."""
        reg = obs.registry()
        n = 4000

        def write(tag, value):
            h = reg.histogram("pipeline.block_s", tag)
            for _ in range(n):
                h.record(value)

        # same (name, tag) instrument from both threads
        t1 = threading.Thread(target=write, args=("", 0.001))
        t2 = threading.Thread(target=write, args=("", 0.004))
        t1.start(); t2.start(); t1.join(); t2.join()
        h = reg.histogram("pipeline.block_s")
        assert h.count == 2 * n
        assert h.sum == pytest.approx(n * 0.001 + n * 0.004)
        assert h.min == pytest.approx(0.001)
        assert h.max == pytest.approx(0.004)

        # sibling tags of the same family, created under the race
        t3 = threading.Thread(target=write, args=("lane-a", 0.002))
        t4 = threading.Thread(target=write, args=("lane-b", 0.003))
        t3.start(); t4.start(); t3.join(); t4.join()
        assert reg.histogram("pipeline.block_s", "lane-a").count == n
        assert reg.histogram("pipeline.block_s", "lane-b").count == n
        snap = reg.snapshot()["histograms"]
        assert snap["pipeline.block_s{lane-a}"]["count"] == n

    def test_kind_conflict_raises(self):
        reg = obs.registry()
        reg.counter("t.kind")
        with pytest.raises(ValueError, match="counter"):
            reg.histogram("t.kind")

    def test_prefix_reset(self):
        reg = obs.registry()
        reg.counter("a.x").inc()
        reg.counter("b.x").inc()
        reg.reset(prefix="a.")
        assert reg.family("a.x") == {}
        assert reg.counter("b.x").value == 1


class TestSpans:
    def test_nesting_and_events(self):
        with obs.span("fit"):
            with obs.span("round", round=1):
                obs.event("mark", k="v")
        tree = obs.span_tree()
        assert tree["name"] == "fit"
        (child,) = tree["children"]
        assert child["name"] == "round"
        assert child["attrs"] == {"round": 1}
        (ev,) = child["events"]
        assert ev["name"] == "mark" and ev["attrs"] == {"k": "v"}

    def test_detached_span_skips_stack(self):
        with obs.span("outer") as outer:
            with obs.span("async_scope", parent=outer.span_id,
                          detached=True):
                # a detached span must NOT become the implicit parent
                assert obs.current_span_id() == outer.span_id
        tree = obs.span_tree()
        assert [c["name"] for c in tree["children"]] == ["async_scope"]

    def test_adopt_stitches_worker_thread(self):
        with obs.span("owner") as owner:
            pid = owner.span_id

            def work():
                with obs.adopt(pid):
                    with obs.span("worker_side"):
                        obs.event("worker_event")

            t = threading.Thread(target=work, name="test-worker")
            t.start()
            t.join()
        tree = obs.span_tree()
        names = _tree_names(tree)
        assert ("worker_side", "test-worker") in names
        assert ("worker_event", "test-worker") in names

    def test_open_span_paths_distinguishes_same_named_threads(self):
        """Concurrent same-named workers (a pool search's prefetch
        threads all share PREFETCH_THREAD_NAME) must each show their
        own open-span path in a hang dump."""
        release = threading.Event()
        ready = []

        def work(tag):
            with obs.span(f"inflight_{tag}"):
                ready.append(tag)
                release.wait(5.0)

        threads = [threading.Thread(target=work, args=(i,),
                                    name="same-name") for i in range(2)]
        for t in threads:
            t.start()
        while len(ready) < 2:
            time.sleep(0.005)
        try:
            paths = obs.open_span_paths()
            inflight = sorted(p for p in paths.values()
                              if p.startswith("inflight_"))
            assert inflight == ["inflight_0", "inflight_1"], paths
            assert all(k.startswith("same-name#") for k in paths
                       if paths[k].startswith("inflight_")), paths
        finally:
            release.set()
            for t in threads:
                t.join()

    def test_disabled_is_noop(self):
        obs.disable()
        try:
            with obs.span("ghost"):
                obs.event("ghost_event")
            assert obs.last_root() is None
            assert obs.span_tree() is None
        finally:
            obs.enable()
        # the event still reached the always-on flight recorder
        assert any(e["name"] == "ghost_event" for e in obs.flight_tail())

    def test_error_recorded_on_span(self):
        with pytest.raises(ValueError):
            with obs.span("failing"):
                raise ValueError("boom")
        tree = obs.span_tree()
        assert tree["name"] == "failing"
        assert "ValueError: boom" in tree["error"]

    def test_clear_spans_drops_records(self):
        with obs.span("gone"):
            pass
        assert obs.last_root() is not None
        obs.clear_spans()
        assert obs.last_root() is None
        assert obs.span_records() == []


def _block_stream(rng, n_blocks=6, rows=64, d=5, parse_s=0.0):
    w = rng.normal(size=d)
    for _ in range(n_blocks):
        if parse_s:
            time.sleep(parse_s)
        X = rng.normal(size=(rows, d)).astype(np.float32)
        yield X, (X @ w > 0).astype(np.int32)


class TestRunReportAcceptance:
    def test_streamed_sgd_fit_single_tree_with_retry_and_quantiles(
            self, tmp_path, rng):
        """Acceptance criterion: run_report() on a depth-2 streamed SGD
        fit = ONE span tree with pipeline stage children, >=1 retry
        event from an injected FaultPlan ingest fault, and registry
        histograms with p50/p99."""
        from dask_ml_tpu import io as dio
        from dask_ml_tpu.linear_model import SGDClassifier
        from dask_ml_tpu.resilience.testing import FaultPlan, fault_plan

        X = rng.normal(size=(500, 5)).astype(np.float32)
        p = tmp_path / "rows.bin"
        X.tofile(p)

        def blocks():
            for xb in dio.stream_binary_blocks(str(p), 100, 5, retries=2):
                yield xb, (xb[:, 0] > 0).astype(np.int32)

        clf = SGDClassifier(random_state=0)
        plan = FaultPlan()
        plan.inject("ingest", at_call=2, times=1)
        with fault_plan(plan):
            _partial.fit(clf, blocks(), prefetch_depth=2,
                         classes=[0, 1])
        assert plan.fired["ingest"] == 1

        rep = diagnostics.run_report()
        tree = rep["span_tree"]
        assert tree["name"] == "fit"
        names = [n for n, _ in _tree_names(tree)]
        for stage in ("pipeline.stream", "pipeline.parse",
                      "pipeline.stage", "pipeline.compute"):
            assert stage in names, f"missing {stage} in {sorted(set(names))}"
        # the absorbed ingest fault left its retry event IN the tree
        assert "resilience.retry" in names
        # registry histograms carry p50/p99
        hist = rep["metrics"]["histograms"]["pipeline.block_s"]
        assert hist["count"] == 5
        assert hist["p50"] > 0 and hist["p99"] >= hist["p50"]
        # legacy reporters unchanged shape, same store
        assert rep["pipeline"]["streams"] == 1
        assert rep["faults"]["retries"]["ingest"] == 1


class TestStitching:
    def test_prefetch_worker_spans_inside_stream_tree(self, rng):
        """Acceptance: the prefetch worker's parse/stage spans stitch
        into the consumer's stream span (thread-adoption rule)."""

        class Sink:
            def partial_fit(self, X, y=None):
                time.sleep(0.001)

        stream_partial_fit(Sink(), _block_stream(rng), depth=2)
        tree = obs.span_tree()
        assert tree["name"] == "pipeline.stream"
        names = _tree_names(tree)
        assert ("pipeline.parse", PREFETCH_THREAD_NAME) in names
        assert ("pipeline.stage", PREFETCH_THREAD_NAME) in names
        assert ("pipeline.compute", "MainThread") in names

    def test_healthy_stream_has_no_error_spans(self, rng):
        """StopIteration ends every stream through the parse span —
        control flow, not a failure: no span of a clean fit may carry
        an error flag (post-mortem filters key on it)."""

        class Sink:
            def partial_fit(self, X, y=None):
                pass

        for depth in (0, 2):
            diagnostics.reset()
            stream_partial_fit(Sink(), _block_stream(rng), depth=depth)
            errors = [n for n in _collect_nodes(obs.span_tree())
                      if n.get("error")]
            assert errors == [], f"depth={depth}: {errors}"

    def test_depth0_stages_on_consumer_thread(self, rng):
        class Sink:
            def partial_fit(self, X, y=None):
                pass

        stream_partial_fit(Sink(), _block_stream(rng), depth=0)
        names = _tree_names(obs.span_tree())
        assert ("pipeline.parse", "MainThread") in names
        assert (("pipeline.parse", PREFETCH_THREAD_NAME)) not in names


class TestJsonlExport:
    def test_round_trip_schema(self, tmp_path, rng):
        path = str(tmp_path / "trace.jsonl")
        obs.disable()
        obs.enable(jsonl_path=path)
        try:
            with obs.span("fit", estimator="X"):
                obs.event("mark", k=1)
        finally:
            obs.disable()
            obs.enable()
        header, records = obs.read_jsonl(path)
        assert header["schema"] == "grafttrace"
        assert header["version"] == obs.SCHEMA_VERSION
        assert {"pid", "unix_time", "perf_counter"} <= set(header)
        kinds = {(r["kind"], r["name"]) for r in records}
        assert ("span", "fit") in kinds and ("event", "mark") in kinds
        for r in records:
            assert {"kind", "span_id", "name", "t0", "t1",
                    "dur_s", "thread"} <= set(r)

    def test_newer_schema_rejected(self, tmp_path):
        path = tmp_path / "future.jsonl"
        path.write_text(json.dumps(
            {"schema": "grafttrace",
             "version": obs.SCHEMA_VERSION + 1}) + "\n")
        with pytest.raises(ValueError, match="newer"):
            obs.read_jsonl(str(path))

    def test_torn_final_line_tolerated(self, tmp_path):
        """kill -9 mid-write leaves a partial last line: the intact
        records must still read back (the crash-forensics contract);
        a torn line ANYWHERE else is corruption and raises."""
        path = str(tmp_path / "torn.jsonl")
        obs.disable()
        obs.enable(jsonl_path=path)
        try:
            with obs.span("kept"):
                pass
        finally:
            obs.disable()
            obs.enable()
        with open(path, "a") as f:
            f.write('{"kind":"span","na')  # the torn tail
        _, records = obs.read_jsonl(path)
        assert [r["name"] for r in records] == ["kept"]
        # mid-file corruption is NOT forgiven
        bad = tmp_path / "mid.jsonl"
        bad.write_text(
            json.dumps({"schema": "grafttrace",
                        "version": obs.SCHEMA_VERSION}) + "\n"
            + '{"torn\n'
            + '{"kind":"event","span_id":1,"parent_id":null,'
              '"name":"x","t0":0,"t1":0,"dur_s":0,"thread":"t"}\n')
        with pytest.raises(ValueError, match="malformed record"):
            obs.read_jsonl(str(bad))

    def test_failed_rearm_keeps_working_sink(self, tmp_path):
        """enable() onto an unwritable path must raise WITHOUT
        destroying the sink that was already streaming."""
        good = str(tmp_path / "good.jsonl")
        obs.disable()
        obs.enable(jsonl_path=good)
        try:
            with pytest.raises(OSError):
                obs.enable(
                    jsonl_path=str(tmp_path / ("x" * 300) / "t.jsonl"))
            with obs.span("still_recorded"):
                pass
        finally:
            obs.disable()
            obs.enable()
        _, records = obs.read_jsonl(good)
        assert any(r["name"] == "still_recorded" for r in records)

    def test_bad_env_trace_path_degrades_to_ring_only(self):
        """An unwritable DASK_ML_TPU_TRACE must not kill the import of
        the traced job: arming degrades to ring-only with a warning
        (the explicit enable(jsonl_path=...) API still raises)."""
        import os
        import subprocess
        import sys

        env = dict(os.environ)
        env["DASK_ML_TPU_TRACE"] = "/proc/nonexistent-dir/t.jsonl"
        r = subprocess.run(
            [sys.executable, "-c",
             "from dask_ml_tpu import obs; "
             "assert obs.enabled(); print('ring-only ok')"],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr[-800:]
        assert "ring-only ok" in r.stdout

    def test_not_a_trace_rejected(self, tmp_path):
        path = tmp_path / "junk.jsonl"
        path.write_text('{"hello": 1}\n')
        with pytest.raises(ValueError, match="grafttrace"):
            obs.read_jsonl(str(path))

    def test_multi_session_append_round_trips(self, tmp_path):
        """The sink appends: two sessions on one path (the documented
        multi-process DASK_ML_TPU_TRACE usage) leave two header lines —
        both validated, neither returned as a record."""
        path = str(tmp_path / "two.jsonl")
        for session in range(2):
            obs.disable()
            obs.enable(jsonl_path=path)
            try:
                with obs.span(f"session{session}"):
                    pass
            finally:
                obs.disable()
        obs.enable()
        header, records = obs.read_jsonl(path)
        assert header["schema"] == "grafttrace"
        names = [r["name"] for r in records]
        assert names == ["session0", "session1"]
        assert all("schema" not in r for r in records)


class TestFlightRecorder:
    def test_step_fault_leaves_post_mortem(self, rng):
        """Satellite acceptance: an injected FaultPlan step fault leaves
        the failed block position in the flight recorder."""
        from dask_ml_tpu.linear_model import SGDClassifier
        from dask_ml_tpu.resilience.testing import (
            FaultInjected, FaultPlan, fault_plan,
        )

        X = rng.normal(size=(600, 5)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        clf = SGDClassifier(random_state=0)
        plan = FaultPlan()
        plan.inject("step", at_call=3, times=1)
        with fault_plan(plan):
            with pytest.raises(FaultInjected):
                _partial.fit(clf, X, y, chunk_size=100,
                             prefetch_depth=2, classes=[0, 1])
        faults = [e for e in obs.flight_tail()
                  if e["name"] == "pipeline.fault"]
        assert faults, "stream fault left no flight event"
        assert faults[-1]["attrs"]["block"] == 2  # blocks 1-2 consumed
        text = obs.flight_post_mortem("test")
        assert "pipeline.fault" in text and "FaultInjected" in text

    def test_dump_shows_open_span_path(self):
        """The watchdog half: a dump taken MID-fit names the open span
        path (which block/round was in flight), not just events."""
        buf = _io.StringIO()
        with obs.span("fit"):
            with obs.span("pipeline.stream"):
                obs.flight_dump(reason="watchdog-test", file=buf)
        out = buf.getvalue()
        assert "watchdog-test" in out
        assert "fit > pipeline.stream" in out

    def test_dump_never_raises(self):
        class Exploding:
            def write(self, *_a, **_k):
                raise OSError("sink died")

            def flush(self):
                raise OSError("sink died")

        obs.flight_dump(file=Exploding())  # must not raise

    def test_tail_bounded(self):
        from dask_ml_tpu.obs import flight

        for i in range(flight.FLIGHT_SIZE + 50):
            obs.event("spam", i=i)
        tail = obs.flight_tail()
        assert len(tail) == flight.FLIGHT_SIZE
        assert tail[-1]["attrs"]["i"] == flight.FLIGHT_SIZE + 49


class TestOverheadAB:
    def test_traced_streamed_fit_overhead_is_bounded(self, rng):
        """What tracing costs a depth-2 streamed SGD fit, in the two
        forms a CPU run can hold still: a COUNT (three records a block,
        parse / stage / compute, beside the fit's few; none while
        disarmed) and a loose RATIO of paired walls that only a cost of
        another order can break (a flush or a lock convoy a record).
        It asserted a median ratio <= 1.03 until PR 30: paired ratios
        of one tree read 0.93 to 1.14 beside five other workers, so 3%
        was inside the clock's own noise and failed about one run in
        ten.  The overhead itself is a chip reading (PERF.md section 6,
        PR 26: +0.8 ms a fit with a session on, +0.03% off).

        The stream wall is pinned by deterministic reader sleeps (the
        pipeline hides compute behind them), so the ratio isolates the
        per-block span/registry cost instead of XLA dispatch noise.
        Estimator: the MEDIAN OF PAIRED PER-ROUND RATIOS.  Each round
        runs both arms back to back (order alternating to cancel any
        systematic first-runner bias) and contributes one on/off ratio;
        a starvation burst lands on both halves of the SAME round or
        skews at most that round's ratio, and the median tolerates up
        to two bad rounds in either direction out of six.
        """
        import statistics

        from dask_ml_tpu.linear_model import SGDClassifier

        n_blocks, parse_s = 30, 0.008  # wall ~0.25 s; 3% >> timer noise
        X0 = rng.normal(size=(128, 5)).astype(np.float32)
        w = rng.normal(size=5)

        def blocks():
            for _ in range(n_blocks):
                time.sleep(parse_s)
                yield X0, (X0 @ w > 0).astype(np.int32)

        def one_fit():
            clf = SGDClassifier(random_state=0)
            t0 = time.perf_counter()
            _partial.fit(clf, blocks(), prefetch_depth=2,
                         classes=[0, 1])
            return time.perf_counter() - t0

        def one_arm(arm):
            if arm == "off":
                obs.disable()
                try:
                    return one_fit()
                finally:
                    obs.enable()
            return one_fit()

        one_fit()  # warm the XLA cache outside both arms

        obs.clear_spans()
        one_arm("off")
        assert obs.span_records() == []
        one_arm("on")
        assert n_blocks <= len(obs.span_records()) <= 4 * n_blocks + 16

        ratios, raw = [], []
        for i in range(6):
            order = ("off", "on") if i % 2 == 0 else ("on", "off")
            walls = {arm: one_arm(arm) for arm in order}
            ratios.append(walls["on"] / walls["off"])
            raw.append(walls)
        med = statistics.median(ratios)
        assert med <= 1.25, (
            f"tracing overhead {med - 1:.2%} (median of paired ratios "
            f"{[round(r, 4) for r in sorted(ratios)]}, raw={raw})"
        )


class TestLegacyReportersAreViews:
    def test_fault_stats_backed_by_registry(self):
        from dask_ml_tpu.resilience.retry import retry

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        assert retry(flaky, retries=3, backoff=0.0, jitter=0.0,
                     tag="obs-test") == "ok"
        snap = diagnostics.fault_stats().snapshot()
        assert snap["faults"]["obs-test"] == 2
        assert snap["retries"]["obs-test"] == 2
        # the SAME counters in the registry (view, not copy)
        assert obs.registry().family("resilience.retry") == {"obs-test": 2}
        assert obs.registry().family("resilience.fault") == {"obs-test": 2}

    def test_private_fault_stats_stay_private(self):
        from dask_ml_tpu.resilience.retry import FaultStats

        private = FaultStats()
        private.record_fault("mine")
        assert private.faults["mine"] == 1
        assert private.total("faults") == 1
        assert obs.registry().family("resilience.fault") == {}
        private.reset()
        assert private.total("faults") == 0

    def test_pipeline_cumulative_is_registry_view(self, rng):
        class Sink:
            def partial_fit(self, X, y=None):
                pass

        stream_partial_fit(Sink(), _block_stream(rng, n_blocks=4),
                           depth=0)
        stream_partial_fit(Sink(), _block_stream(rng, n_blocks=4),
                           depth=0)
        rep = diagnostics.pipeline_report()
        assert rep["streams"] == 2
        assert rep["cumulative"]["blocks"] == 8
        assert obs.registry().counter("pipeline.streams").value == 2
        hist = obs.registry().histogram("pipeline.wall_s")
        assert hist.count == 2

    def test_diagnostics_reset_clears_everything(self, rng):
        class Sink:
            def partial_fit(self, X, y=None):
                pass

        stream_partial_fit(Sink(), _block_stream(rng, n_blocks=2),
                           depth=0)
        diagnostics.fault_stats().record_fault("x")
        obs.event("e")
        diagnostics.reset()
        assert diagnostics.pipeline_report() == {"streams": 0}
        assert diagnostics.fault_stats().snapshot()["faults"] == {}
        assert obs.metrics_snapshot() == {
            "counters": {}, "gauges": {}, "histograms": {}}
        assert obs.flight_tail() == []
        assert obs.span_tree() is None


class TestTraceExceptionSafety:
    def test_failed_start_does_not_mask_error(self, monkeypatch):
        """Satellite: if start_trace raises, the REAL error propagates
        and stop_trace is never called on a never-started trace."""
        import jax

        stopped = {"n": 0}

        def bad_start(_dir):
            raise RuntimeError("trace dir unwritable")

        monkeypatch.setattr(jax.profiler, "start_trace", bad_start)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: stopped.__setitem__("n",
                                                        stopped["n"] + 1))
        with pytest.raises(RuntimeError, match="trace dir unwritable"):
            with diagnostics.trace("/nonexistent"):
                pass  # pragma: no cover - never reached
        assert stopped["n"] == 0

    def test_stop_runs_on_body_failure(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append("start"))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append("stop"))
        with pytest.raises(ValueError):
            with diagnostics.trace("/tmp/x"):
                raise ValueError("body failed")
        assert calls == ["start", "stop"]


# -- ISSUE 26: a profiler session arms the spans -------------------------

def _small_logistic(rows=600, features=5, seed=0):
    r = np.random.default_rng(seed)
    X = r.normal(size=(rows, features)).astype(np.float32)
    y = (X @ r.normal(size=features) + r.normal(size=rows) > 0)
    return X, y.astype(np.float32)


def _host_events(trace_dir):
    """``{name: [(start_ns, end_ns, {stat: value}), ...]}`` of the host
    plane of the newest ``.xplane.pb`` under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


class TestProfilerSessionArmsSpans:
    @pytest.fixture(scope="class")
    def traced(self, tmp_path_factory):
        """One ADMM fit inside a profiler session with recording NOT
        enabled: what the rings and the session's file hold."""
        import jax

        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = _small_logistic()
        trace_dir = str(tmp_path_factory.mktemp("session"))
        diagnostics.reset()
        obs.disable()
        try:
            before = obs.span("ghost")
            jax.profiler.start_trace(trace_dir)
            try:
                est = LogisticRegression(solver="admm").fit(X, y)
                with obs.span("t.parent") as parent:
                    with obs.span("t.detached", detached=True,
                                  parent=parent.span_id):
                        pass
                    t = time.perf_counter()
                    obs.record_span("t.retro", t - 1e-3, t)
            finally:
                jax.profiler.stop_trace()
            after = obs.span("ghost")
            records = [r.as_dict() for r in obs.span_records()]
            counters = obs.metrics_snapshot()["counters"]
        finally:
            obs.enable()
        return {"est": est, "records": records, "noops": (before, after),
                "host": _host_events(trace_dir), "counters": counters}

    def test_span_is_the_noop_outside_the_session(self, traced):
        before, after = traced["noops"]
        assert before is after and before.span_id is None
        assert before.set(anything=1) is None  # set() is a no-op on it
        with before as entered:
            assert entered is before

    def test_fit_records_one_root_with_its_three_children(self, traced):
        spans = [r for r in traced["records"] if r["kind"] == "span"]
        roots = [r for r in spans if r["name"] == "glm.fit"]
        assert len(roots) == 1 and roots[0]["parent_id"] is None
        root = roots[0]
        kids = [r for r in spans if r["parent_id"] == root["span_id"]]
        assert [k["name"] for k in kids] == [
            "glm.classes", "glm.prepare", "glm.solve"]
        at = root["t0"]
        for k in kids:  # in that order, not overlapping, inside the root
            assert at <= k["t0"] <= k["t1"] <= root["t1"]
            at = k["t1"]
        X, _ = _small_logistic()
        assert root["attrs"] == {
            "estimator": "LogisticRegression", "solver": "admm",
            "rows": X.shape[0], "features": X.shape[1], "chips": 8,
            "n_shards": 8, "classes": 2}
        assert kids[0]["attrs"] == {"classes": 2}
        assert kids[1]["attrs"]["padded_rows"] >= X.shape[0]

    def test_xplane_holds_the_spans_nested_under_one_fit_id(self, traced):
        host = traced["host"]
        names = ("glm.fit", "glm.classes", "glm.prepare", "glm.solve")
        for name in names:
            assert len(host[name]) == 1, name
        (f0, f1, fstats), = host["glm.fit"]
        root = next(r for r in traced["records"] if r["name"] == "glm.fit")
        at = f0
        for name in names[1:]:
            (s, e, stats), = host[name]
            assert at <= s <= e <= f1
            assert stats["fit"] == root["span_id"]
            at = e
        assert fstats["fit"] == root["span_id"]
        assert fstats["estimator"] == "LogisticRegression"
        # the annotation spans the same interval as the ring record, on
        # another clock: their durations agree
        assert (f1 - f0) / 1e9 == pytest.approx(root["dur_s"], abs=2e-3)

    def test_set_reaches_the_open_annotation(self, traced):
        (_, _, stats), = traced["host"]["glm.solve"]
        solve = next(r for r in traced["records"] if r["name"] == "glm.solve")
        for count in ("rounds", "inner_iters", "passes", "trials"):
            assert stats[count] == solve["attrs"][count] > 0
        (_, _, fstats), = traced["host"]["glm.fit"]
        assert fstats["rows"] == 600  # set() after entering, on the root

    def test_detached_and_retroactive_spans_stay_ring_only(self, traced):
        ring = {r["name"] for r in traced["records"]}
        assert {"t.parent", "t.detached", "t.retro"} <= ring
        assert "t.parent" in traced["host"]
        assert "t.detached" not in traced["host"]
        assert "t.retro" not in traced["host"]

    def test_counts_on_the_span_and_in_the_registry(self, traced):
        est = traced["est"]
        solve = next(r for r in traced["records"] if r["name"] == "glm.solve")
        a = solve["attrs"]
        assert a["rounds"] == int(est.n_iter_[0])
        assert a["passes"] >= a["rounds"] + a["inner_iters"]
        c = traced["counters"]
        assert c["solve.count"] == 1
        for count in ("rounds", "inner_iters", "passes", "trials"):
            assert c[f"solve.{count}"] / c["solve.count"] == a[count]


class TestFitSpansAndCounts:
    def _solve_span(self):
        return next(c for c in obs.span_tree()["children"]
                    if c["name"] == "glm.solve")

    def test_identical_fits_count_the_same(self):
        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = _small_logistic()
        seen = []
        for _ in range(2):
            LogisticRegression(solver="admm").fit(X, y)
            seen.append(self._solve_span()["attrs"])
        assert seen[0] == seen[1] and seen[0]["passes"] > 0

    @pytest.mark.parametrize("estimator,solver", [
        ("LogisticRegression", "lbfgs"), ("LinearRegression", "lbfgs"),
        ("LinearRegression", "admm")])
    def test_counted_solvers_report_all_three(self, estimator, solver):
        import dask_ml_tpu.linear_model as lm

        X, y = _small_logistic()
        est = getattr(lm, estimator)(solver=solver).fit(X, y)
        a = self._solve_span()["attrs"]
        assert a["rounds"] == int(est.n_iter_[0])
        if solver == "lbfgs":
            assert a["inner_iters"] == a["rounds"]
        assert a["passes"] >= a["rounds"] + a["inner_iters"] - (
            a["rounds"] if solver == "lbfgs" else 0)
        tree = obs.span_tree()
        assert tree["name"] == "glm.fit"
        want = (["glm.classes"] if estimator == "LogisticRegression"
                else []) + ["glm.prepare", "glm.solve"]
        assert [c["name"] for c in tree["children"]] == want

    def test_uncounted_solver_reports_its_rounds_only(self):
        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = _small_logistic()
        est = LogisticRegression(solver="newton").fit(X, y)
        a = self._solve_span()["attrs"]
        assert a["rounds"] == int(est.n_iter_[0])
        assert "passes" not in a and "inner_iters" not in a

    def test_compile_lands_on_the_span_that_compiled(self):
        """A first fit at a new program shows ``compile`` events under
        ``glm.solve``; the same fit again shows none anywhere."""
        from dask_ml_tpu.linear_model import LogisticRegression

        X, y = _small_logistic(rows=333, features=7)
        # a static argument no other test uses: a fresh _admm_run
        kw = dict(solver="admm", solver_kwargs={"inner_iter": 13})
        LogisticRegression(**kw).fit(X, y)
        events = self._solve_span()["events"]
        assert [e["name"] for e in events].count("compile") >= 1
        assert all(e["attrs"]["duration_s"] > 0 for e in events)
        LogisticRegression(**kw).fit(X, y)
        assert "compile" not in [n for n, _ in _tree_names(obs.span_tree())]
