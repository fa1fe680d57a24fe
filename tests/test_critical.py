"""graftpath tests (ISSUE 15 tentpole): the causal critical-path
engine, its joins, and the acceptance criteria.

Covers: the interval-algebra layering on synthetic timelines (category
times sum to the wall EXACTLY, priority order is causal);
``run_report()["critical_path"]`` present with a non-"unknown" verdict
for a depth-2 streamed SGD fit, a concurrent Hyperband search, and a
serve closed-loop run; the per-request serve split pinned
(queue+window+device+fetch == request_s) under an armed sanitizer with
zero steady compiles; the data plane's reorder-queue wait counting as
FED (not idle) under graftscope; the ``data.*`` / ``search.round_s``
families scraping through ``/metrics`` as valid Prometheus text; the
and the flight-recorder dump showing OPEN device intervals.
"""

import json
import re
import time
import urllib.request

import numpy as np
import pytest

from dask_ml_tpu import diagnostics, obs
from dask_ml_tpu.obs import critical, flight, scope
from dask_ml_tpu.obs.spans import SpanRecord
from dask_ml_tpu.pipeline import stream_partial_fit


@pytest.fixture(autouse=True)
def _clean_books():
    if not obs.enabled():
        obs.enable()
    diagnostics.reset()
    yield
    obs.serve.stop()
    diagnostics.reset()


class _Leaf:
    def __init__(self, ready=False):
        self._ready = ready

    def is_ready(self):
        return self._ready


def _sgd_blocks(n_blocks=8, rows=16384, dim=32, parse_s=0.001, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(rows, dim)).astype(np.float32)
    w = rng.normal(size=dim)
    y = (X @ w > 0).astype(np.int32)
    for _ in range(n_blocks):
        if parse_s:
            time.sleep(parse_s)
        yield X, y


def _rec(name, t0, t1, span_id, parent_id=1, thread="t"):
    return SpanRecord("span", span_id, parent_id, name, t0, t1, thread,
                      {})


# -- the engine on synthetic timelines -----------------------------------

class TestIntervalAlgebra:
    def test_union_merges_and_sorts(self):
        u = critical._union([(5, 7), (1, 2), (1.5, 3), (7, 7)])
        assert u == [(1, 3), (5, 7)]
        assert critical._length(u) == pytest.approx(4.0)

    def test_overlap_two_pointer(self):
        xs = [(0, 4), (6, 9)]
        ys = [(2, 7), (8, 12)]
        assert critical._overlap(xs, ys) == pytest.approx(
            2 + 1 + 1)  # [2,4] + [6,7] + [8,9]

    def test_resolvers_strict_parse(self, monkeypatch):
        monkeypatch.setenv(critical.CRITICAL_TOL_ENV, "nope")
        with pytest.raises(ValueError, match="must be a number"):
            critical.resolve_tolerance()
        monkeypatch.setenv(critical.CRITICAL_TOL_ENV, "1.5")
        with pytest.raises(ValueError, match="in \\(0, 1\\)"):
            critical.resolve_tolerance()
        monkeypatch.setenv(critical.CRITICAL_TOL_ENV, "0.2")
        assert critical.resolve_tolerance() == 0.2
        assert critical.resolve_dominance(0.5) == 0.5


class TestSyntheticCriticalPath:
    def test_priority_layering_sums_to_wall_exactly(self):
        """Synthetic window [0, 10]: device [0,4] under a consumer
        compute span [0,4], a worker parse span [3,6] (1s hidden under
        the concurrent compute), stage [6,7], a stall [7,9] with no
        producer work over it, nothing in [9,10]."""
        root = _rec("pipeline.stream", 0.0, 10.0, 1, None)
        records = [
            root,
            _rec("pipeline.compute", 0.0, 4.0, 5, thread="consumer"),
            _rec("pipeline.parse", 3.0, 6.0, 2, thread="worker"),
            _rec("pipeline.stage", 6.0, 7.0, 3, thread="worker"),
            _rec("pipeline.stall", 7.0, 9.0, 4, thread="consumer"),
        ]
        device = [{"program": "p", "t0": 0.0, "t1": 4.0, "seq": 0}]
        cp = critical.critical_path(root, records=records,
                                    device=device, publish=False)
        cats = cp["categories"]
        assert cats["device"] == pytest.approx(4.0)
        assert cats["parse"] == pytest.approx(2.0)   # [4,6]: 1s hidden
        assert cats["stage"] == pytest.approx(1.0)
        assert cats["queue_wait"] == pytest.approx(2.0)
        assert cats["dispatch"] == pytest.approx(0.0)  # under device
        assert cats["idle_gap"] == pytest.approx(1.0)
        assert sum(cats.values()) == pytest.approx(cp["wall_s"])
        assert cp["within_tolerance"]
        assert cp["verdict"]["class"] == "device-bound"
        # worker host time [3,7] = 4s; [3,4] ran under the consumer's
        # concurrent compute span → 1s hidden
        assert cp["overlap_efficiency"] == pytest.approx(1.0 / 4.0)
        assert cp["plane"] == "fit"

    def test_depth0_single_thread_measures_zero_overlap(self):
        """Serial layout (everything on one thread): no overlap, even
        when a slack-extended device interval laps the next parse."""
        root = _rec("pipeline.stream", 0.0, 10.0, 1, None)
        records = [
            root,
            _rec("pipeline.compute", 0.0, 4.0, 2, thread="main"),
            _rec("pipeline.parse", 4.0, 6.0, 3, thread="main"),
        ]
        # device interval closed LATE (detection slack): laps the parse
        device = [{"program": "p", "t0": 0.0, "t1": 5.0, "seq": 0}]
        cp = critical.critical_path(root, records=records,
                                    device=device, publish=False)
        assert cp["overlap_efficiency"] == pytest.approx(0.0)

    def test_stall_covered_by_producer_work_attributes_to_cause(self):
        """A consumer stall overlapped by the worker's concurrent parse
        attributes to PARSE (the cause), not queue_wait."""
        root = _rec("pipeline.stream", 0.0, 10.0, 1, None)
        records = [
            root,
            _rec("pipeline.parse", 0.0, 8.0, 2, thread="worker"),
            _rec("pipeline.stall", 1.0, 7.0, 3, thread="consumer"),
        ]
        cp = critical.critical_path(root, records=records, device=[],
                                    publish=False)
        assert cp["categories"]["parse"] == pytest.approx(8.0)
        assert cp["categories"]["queue_wait"] == pytest.approx(0.0)
        assert cp["verdict"]["class"] == "parse-bound"

    def test_reader_truth_outranks_reorder_wait(self):
        """The worker's pipeline.parse wraps a reorder WAIT; the reader
        threads' data.parse is the concurrent truth — reader work
        claims its time, the uncovered wait is queue_wait, and the
        wrapper's residue stays parse."""
        root = _rec("pipeline.stream", 0.0, 10.0, 1, None)
        records = [
            root,
            # worker "parse" wrapping the whole pull (mostly waiting)
            _rec("pipeline.parse", 0.0, 10.0, 2, thread="worker"),
            # the wait itself, and the readers' real work over part
            _rec("data.queue_wait", 0.0, 8.0, 3, thread="worker"),
            _rec("data.parse", 0.0, 5.0, 4, thread="reader"),
        ]
        cp = critical.critical_path(root, records=records, device=[],
                                    publish=False)
        assert cp["categories"]["parse"] == pytest.approx(
            5.0 + 2.0)  # reader truth + wrapper residue [8,10]
        assert cp["categories"]["queue_wait"] == pytest.approx(3.0)
        assert cp["verdict"]["class"] == "parse-bound"

    def test_idle_dominant_refuses_verdict(self):
        root = _rec("pipeline.stream", 0.0, 10.0, 1, None)
        records = [root, _rec("pipeline.parse", 0.0, 1.0, 2)]
        cp = critical.critical_path(root, records=records, device=[],
                                    publish=False)
        assert cp["shares"]["idle_gap"] > 0.5
        assert cp["verdict"]["class"] == "unknown"
        assert "idle_gap" in cp["verdict"]["reason"]

    def test_container_spans_are_not_host_work(self):
        """A search.round container covering the window must not read
        as dispatch; an inner search.unit does."""
        root = _rec("search.fit", 0.0, 10.0, 1, None)
        records = [
            root,
            _rec("search.round", 0.0, 10.0, 2),
            _rec("search.unit", 0.0, 6.0, 3),
        ]
        cp = critical.critical_path(root, records=records, device=[],
                                    publish=False)
        assert cp["categories"]["dispatch"] == pytest.approx(6.0)
        assert cp["categories"]["idle_gap"] == pytest.approx(4.0)
        assert cp["plane"] == "search"

    def test_no_root_no_serve_is_explicit_unknown(self):
        obs.clear_spans()
        cp = critical.critical_path(publish=False)
        assert cp["plane"] is None
        assert cp["verdict"]["class"] == "unknown"

    def test_publish_lands_gauges_and_device_report_join(self):
        root = _rec("pipeline.stream", 0.0, 10.0, 1, None)
        device = [{"program": "p", "t0": 0.0, "t1": 9.0, "seq": 0}]
        cp = critical.critical_path(root, records=[root],
                                    device=device)
        assert cp["verdict"]["class"] == "device-bound"
        reg = obs.registry()
        assert reg.gauge("critical.bottleneck", "fit").value == \
            float(critical.BOTTLENECK_CLASSES.index("device-bound"))
        dev = scope.device_report()
        assert dev["critical"]["fit"]["verdict"] == "device-bound"
        # …and the gauge scrapes as valid Prometheus text
        text = obs.prometheus_text()
        assert "# TYPE critical_bottleneck gauge" in text
        assert 'critical_bottleneck{tag="fit"} 1.0' in text


# -- acceptance: the three planes ----------------------------------------

class TestRunReportCriticalPath:
    def test_depth2_streamed_fit_has_verdict(self):
        from dask_ml_tpu.linear_model import SGDClassifier

        model = SGDClassifier(random_state=0)
        stream_partial_fit(model, _sgd_blocks(4), depth=2,
                           fit_kwargs={"classes": np.array([0, 1])})
        diagnostics.reset()  # scope to the measured fit
        stream_partial_fit(model, _sgd_blocks(6), depth=2,
                           fit_kwargs={"classes": np.array([0, 1])})
        cp = diagnostics.run_report()["critical_path"]
        assert cp["plane"] == "fit"
        cats = cp["categories"]
        assert sum(cats.values()) == pytest.approx(
            cp["wall_s"], rel=cp["tolerance"])
        assert cp["within_tolerance"]
        assert cp["verdict"]["class"] != "unknown"
        assert cp["verdict"]["class"] in critical.BOTTLENECK_CLASSES
        # a share of host time, whatever this box's clock made of it
        assert 0.0 <= cp["overlap_efficiency"] <= 1.0
        assert cp["evidence"]["top_spans"]

    @pytest.mark.slow
    def test_concurrent_hyperband_search_has_verdict(self):
        from dask_ml_tpu.linear_model import SGDClassifier
        from dask_ml_tpu.model_selection import HyperbandSearchCV

        rng = np.random.RandomState(3)
        X = rng.normal(size=(20_000, 16)).astype(np.float32)
        y = (X @ rng.normal(size=16) > 0).astype(np.int32)
        hb = HyperbandSearchCV(
            SGDClassifier(random_state=0),
            {"loss": ["log_loss", "hinge"],
             "alpha": [1e-4, 1e-3, 1e-2]},
            max_iter=9, random_state=0, test_size=0.25)
        hb.fit(X, y, classes=np.array([0, 1]))
        cp = diagnostics.run_report()["critical_path"]
        assert cp["plane"] == "search"
        assert cp["root"] == "search.fit"
        assert sum(cp["categories"].values()) == pytest.approx(
            cp["wall_s"], rel=cp["tolerance"])
        assert cp["verdict"]["class"] != "unknown"

    def test_serve_closed_loop_has_verdict(self):
        from dask_ml_tpu.linear_model import SGDClassifier
        from dask_ml_tpu.serve import ModelServer

        rng = np.random.RandomState(5)
        X = rng.normal(size=(256, 8)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        model = SGDClassifier(random_state=0)
        model.partial_fit(X, y, classes=np.array([0, 1]))
        diagnostics.reset()  # no fit root: the serve fallback path
        with ModelServer(label="t_cp", window_s=0.0) as srv:
            srv.load("m", model)
            for i in range(30):
                srv.predict("m", X[i % 64:i % 64 + 1])
        cp = diagnostics.run_report()["critical_path"]
        assert cp["plane"] == "serve"
        assert cp["requests"] >= 30
        assert cp["within_tolerance"]
        assert cp["verdict"]["class"] != "unknown"
        assert set(cp["categories"]) == {"queue", "window", "device",
                                         "fetch"}


class TestServePerRequestSplit:
    def test_split_pinned_under_armed_sanitizer(self, sanitizer):
        """Acceptance criterion: queue+window+device+fetch ==
        request_s (same-clock contiguous stamps, so the identity is
        exact, not approximate) under an armed sanitizer with zero
        steady compiles."""
        from dask_ml_tpu.linear_model import SGDClassifier
        from dask_ml_tpu.serve import ModelServer

        rng = np.random.RandomState(11)
        X = rng.normal(size=(512, 16)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        model = SGDClassifier(random_state=0)
        model.partial_fit(X, y, classes=np.array([0, 1]))
        reg = obs.registry()
        with ModelServer(label="t_split", window_s=0.0) as srv:
            srv.load("m", model)
            srv.predict("m", X[:1])  # request path hot
            reg.reset(prefix="serve.request_s")
            reg.reset(prefix="serve.req_")
            with sanitizer.steady():
                for i in range(40):
                    srv.predict("m", X[i:i + 1])
        rep = sanitizer.report()
        assert rep["totals"]["steady_compiles"] == 0, rep["violations"]
        total = sum(
            reg.histogram(f"serve.req_{leg}_s", "m").sum
            for leg in ("queue", "window", "device", "fetch"))
        req = reg.histogram("serve.request_s", "m")
        assert req.count == 40
        assert reg.histogram("serve.req_queue_s", "m").count == 40
        assert total == pytest.approx(req.sum, rel=1e-6)
        sc = obs.serve_critical(publish=False)
        assert sc["within_tolerance"] and sc["coverage"] == \
            pytest.approx(1.0, abs=1e-3)

    def test_slowest_request_exemplar_in_flight_recorder(self):
        from dask_ml_tpu.linear_model import SGDClassifier
        from dask_ml_tpu.serve import ModelServer

        rng = np.random.RandomState(2)
        X = rng.normal(size=(64, 8)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        model = SGDClassifier(random_state=0)
        model.partial_fit(X, y, classes=np.array([0, 1]))
        with ModelServer(label="t_ex", window_s=0.0) as srv:
            srv.load("m", model)
            for i in range(10):
                srv.predict("m", X[i:i + 1])
        events = [e for e in flight.tail()
                  if e["name"] == "serve.slow_request"]
        assert events, "no slow-request exemplar recorded"
        ex = events[-1]["attrs"]
        # the exemplar carries the trace id and the full split
        assert ex["request"] >= 1 and ex["model"] == "m"
        parts = (ex["queue_ms"] + ex["window_ms"] + ex["device_ms"]
                 + ex["fetch_ms"])
        # each leg is rounded to a microsecond in the exemplar: the
        # identity holds to the rounding, not exactly
        assert parts == pytest.approx(ex["request_ms"], abs=0.005)


# -- the data plane (satellites 2 and 4) ---------------------------------

def _tiny_dataset(tmp_path, rows=4096, dim=8, shards=2,
                  block_rows=256):
    from dask_ml_tpu import data as _data

    rng = np.random.RandomState(0)
    X = rng.normal(size=(rows, dim)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    d = str(tmp_path / "ds")
    _data.write_dataset(d, X, y, shards=shards, block_rows=block_rows)
    return d


class TestDataPlaneHonesty:
    def test_reorder_queue_wait_counts_as_fed_not_idle(self, tmp_path):
        """Satellite: the honesty contract asserted for search
        queue-wait, applied to the data plane — while the consumer
        waits on the reorder queue behind slow readers, an in-flight
        device program keeps the graftscope lane BUSY (enqueue→ready):
        the wait reads as fed, never as device idle."""
        from dask_ml_tpu import data as _data

        d = _tiny_dataset(tmp_path)
        leaf = _Leaf(ready=False)
        cur = scope.cursor()
        scope.track("prog.during_ingest", time.perf_counter(), [leaf])
        ds = _data.ShardedDataset(d, key=0, readers=2,
                                  fetch_latency_s=0.005,
                                  label="fed_test")
        n = sum(xb.shape[0] for xb, yb in ds.iter_blocks(epoch=0))
        assert n == 4096
        leaf._ready = True
        assert scope.settle(5.0)
        dev = scope.device_report(since=cur)
        # ONE interval spanning the whole (slow, wait-heavy) stream:
        # zero idle, utilization 1.0 — queue wait counted as FED
        assert dev["dispatches"] == 1
        assert dev["utilization"] == pytest.approx(1.0)
        assert dev["idle_s"] == pytest.approx(0.0, abs=1e-6)
        assert dev["idle_gaps"] == []
        # …and the wait itself was measured on its own books
        qw = obs.registry().histogram("data.queue_wait_s", "fed_test")
        assert qw.count >= 1 and qw.sum > 0.0

    def test_data_families_and_search_round_scrape_via_endpoint(
            self, tmp_path):
        """Satellite: the data.* reader/reorder metrics and the
        search.round_s histogram export through a live /metrics
        endpoint as valid Prometheus text."""
        from dask_ml_tpu import data as _data
        from dask_ml_tpu.obs import serve as obs_serve

        d = _tiny_dataset(tmp_path)
        ds = _data.ShardedDataset(d, key=0, readers=2,
                                  fetch_latency_s=0.002,
                                  label="scrape_test")
        list(ds.iter_blocks(epoch=0))
        obs.registry().histogram("search.round_s").record(0.05)
        srv = obs_serve.start(port=0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=10) as resp:
            assert resp.status == 200
            text = resp.read().decode()
        # Prometheus validity: every line is a TYPE comment or a
        # sample with a legal name, optional labels, numeric value
        sample = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
            r'(\{[a-zA-Z0-9_]+="(\\.|[^"\\])*"'
            r'(,[a-zA-Z0-9_]+="(\\.|[^"\\])*")*\})? '
            r"(NaN|[-+0-9.e]+)$")
        for line in text.strip().splitlines():
            if line.startswith("# TYPE "):
                assert re.match(
                    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                    r"(counter|gauge|summary)$", line), line
            else:
                assert sample.match(line), line
        assert "# TYPE data_blocks counter" in text
        assert 'data_blocks{tag="scrape_test"} 16.0' in text
        assert 'data_rows{tag="scrape_test"} 4096.0' in text
        assert "# TYPE data_queue_wait_s summary" in text
        assert "# TYPE search_round_s summary" in text
        assert re.search(r'search_round_s\{quantile="0\.5"\}', text)
        assert "search_round_s_count 1" in text


# -- flight recorder (satellite 3) ---------------------------------------

class TestForensicJoins:
    def test_flight_dump_shows_open_device_interval(self):
        leaf = _Leaf(ready=False)
        scope.track("prog.hung", time.perf_counter(), [leaf])
        try:
            text = flight.post_mortem("unit test")
            assert "open device intervals:" in text
            assert "prog.hung: in flight" in text
        finally:
            leaf._ready = True
            scope.settle(5.0)
        # once closed, the dump says so explicitly
        assert "open device intervals: (none)" in \
            flight.post_mortem("after")
