"""graftscope tests (ISSUE 10 tentpole): device-time accounting and
the live metrics endpoint.

Covers the acceptance criteria:
``run_report()["device"]["utilization"]`` > 0.5 on a depth-2 streamed
SGD fit; ``GET /metrics`` during a fit returns valid Prometheus
text including ``device_busy_s`` and ``pipeline_block_s`` quantiles
from a supervisor-registered, graftsan-clean endpoint thread.
"""

import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from dask_ml_tpu import diagnostics, obs
from dask_ml_tpu.obs import scope, serve
from dask_ml_tpu.pipeline import stream_partial_fit
from dask_ml_tpu.resilience import supervisor


@pytest.fixture(autouse=True)
def _clean_books():
    """Book isolation; also stop any endpoint a test left running, and
    keep span recording armed (the conftest arms it session-wide, but
    an earlier suite's A/B may have left it disabled — the acceptance
    tests need host spans next to the device lane)."""
    if not obs.enabled():
        obs.enable()
    diagnostics.reset()
    yield
    serve.stop()
    diagnostics.reset()


class _Leaf:
    """A fake dispatch output leaf with a settable readiness flag."""

    def __init__(self, ready=False):
        self._ready = ready

    def is_ready(self):
        return self._ready


class _RaisingLeaf:
    def is_ready(self):
        raise RuntimeError("donated buffer")


def _sgd_blocks(n_blocks=8, rows=16384, dim=32, parse_s=0.001, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(rows, dim)).astype(np.float32)
    w = rng.normal(size=dim)
    y = (X @ w > 0).astype(np.int32)
    for _ in range(n_blocks):
        if parse_s:
            time.sleep(parse_s)
        yield X, y


def _fit_streamed_sgd(depth=2, n_blocks=8):
    from dask_ml_tpu.linear_model import SGDClassifier

    model = SGDClassifier(random_state=0)
    stream_partial_fit(model, _sgd_blocks(n_blocks), depth=depth,
                       fit_kwargs={"classes": np.array([0, 1])})
    return model


# -- device-time accounting (obs/scope.py) -------------------------------

class TestScope:
    def test_track_and_sweep_close_interval(self):
        leaf = _Leaf(ready=False)
        t0 = time.perf_counter()
        assert scope.track("prog.a", t0, [leaf])
        assert scope.pending_count() == 1
        leaf._ready = True
        scope.sweep()
        assert scope.pending_count() == 0
        ivs = [iv for iv in scope.timeline() if iv["program"] == "prog.a"]
        assert len(ivs) == 1 and not ivs[0].get("open")
        assert ivs[0]["t1"] >= ivs[0]["t0"] == t0
        reg = obs.registry()
        assert reg.counter("device.dispatches", "prog.a").value == 1
        assert reg.histogram("device.busy_s", "prog.a").count == 1

    def test_tracer_outputs_are_not_dispatches(self):
        # leaves without is_ready (tracers — a program inlining into an
        # outer trace) must not open an interval or count a dispatch
        assert not scope.track("prog.traced", time.perf_counter(),
                               [object(), 3.0])
        assert scope.pending_count() == 0
        assert obs.registry().family("device.dispatches") == {}

    def test_raising_is_ready_counts_as_ready(self):
        # a donated buffer's is_ready raises: treat as ready, the
        # consuming program's own interval keeps the lane continuous
        assert scope.track("prog.donate", time.perf_counter(),
                           [_RaisingLeaf()])
        scope.sweep()
        assert scope.pending_count() == 0

    def test_open_interval_visible_in_timeline(self):
        leaf = _Leaf(ready=False)
        scope.track("prog.open", time.perf_counter(), [leaf])
        ivs = [iv for iv in scope.timeline()
               if iv["program"] == "prog.open"]
        assert len(ivs) == 1 and ivs[0]["open"] is True
        leaf._ready = True  # let the sampler retire it

    def test_settle_times_out_on_wedged_program(self):
        leaf = _Leaf(ready=False)
        scope.track("prog.wedged", time.perf_counter(), [leaf])
        assert scope.settle(timeout_s=0.05) is False
        leaf._ready = True
        assert scope.settle(timeout_s=2.0) is True

    def test_absorb_is_reentrant_and_thread_local(self):
        assert not scope.absorbed()
        with scope.absorb():
            assert scope.absorbed()
            with scope.absorb():
                assert scope.absorbed()
            assert scope.absorbed()
        assert not scope.absorbed()
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(scope.absorbed()))
        with scope.absorb():
            t.start()
            t.join()
        assert seen == [False]  # absorption never leaks across threads

    def test_cursor_scopes_device_report(self):
        a = _Leaf(ready=True)
        scope.track("prog.before", time.perf_counter(), [a])
        scope.sweep()
        cur = scope.cursor()
        b = _Leaf(ready=True)
        scope.track("prog.after", time.perf_counter(), [b])
        scope.sweep()
        rep = scope.device_report(since=cur)
        assert set(rep["programs"]) == {"prog.after"}
        assert rep["dispatches"] == 1

    def test_device_report_merges_overlaps_and_ranks_gaps(self):
        # hand-build the timeline through the public API: two
        # overlapping busy intervals, a gap, then a third
        base = time.perf_counter()
        for name, dt0, dur in (("p", 0.00, 0.10), ("q", 0.05, 0.10),
                               ("p", 0.45, 0.05)):
            leaf = _Leaf(ready=True)
            with scope._COND:
                scope._PENDING.append(
                    scope._Pending(name, base + dt0, [leaf], scope._SEQ))
                scope._SEQ += 1
                scope._sweep_locked(base + dt0 + dur)
        rep = scope.device_report()
        assert rep["dispatches"] == 3
        assert rep["busy_s"] == pytest.approx(0.20, abs=1e-6)
        assert rep["window_s"] == pytest.approx(0.50, abs=1e-6)
        assert rep["idle_s"] == pytest.approx(0.30, abs=1e-6)
        assert rep["utilization"] == pytest.approx(0.40, abs=1e-3)
        assert len(rep["idle_gaps"]) == 1
        assert rep["idle_gaps"][0]["dur_s"] == pytest.approx(0.30,
                                                            abs=1e-6)
        assert rep["programs"]["p"]["dispatches"] == 2

    def test_empty_report_shape(self):
        rep = scope.device_report()
        assert rep == {"dispatches": 0, "busy_s": 0.0, "window_s": 0.0,
                       "idle_s": 0.0, "utilization": 0.0,
                       "idle_gaps": [], "programs": {}, "pending": 0}

    def test_reset_drops_timeline_keeps_nothing_pending(self):
        scope.track("prog.r", time.perf_counter(), [_Leaf(ready=True)])
        scope.sweep()
        assert scope.timeline()
        scope.reset()
        assert scope.timeline() == []
        assert scope.pending_count() == 0

    def test_sampler_closes_interval_without_host_activity(self):
        """The end of a busy period is found even when the host goes
        quiet: no further track/sweep calls — the sampler thread must
        retire the pending interval on its own."""
        leaf = _Leaf(ready=False)
        scope.track("prog.sampler", time.perf_counter(), [leaf])
        leaf._ready = True
        deadline = time.monotonic() + 5.0
        while scope.pending_count() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert scope.pending_count() == 0
        sampler = supervisor.lookup(scope.SCOPE_THREAD_NAME)
        assert sampler is not None or scope._SAMPLER.is_alive()

    def test_sampler_thread_is_host_only_named(self):
        from dask_ml_tpu.analysis.rules._spmd import (
            HOST_ONLY_THREAD_NAMES)

        assert scope.SCOPE_THREAD_NAME in HOST_ONLY_THREAD_NAMES


# -- acceptance: streamed fit occupancy -----------------------------------

class TestStreamedFitAcceptance:
    def test_depth2_sgd_utilization(self):
        """Acceptance criterion: a depth-2 streamed SGD fit fills
        run_report()["device"]: every block's dispatch counted, busy and
        idle adding up to the window, utilization the busy share of it.
        (It asserted utilization > 0.5 until PR 30: a share of CPU wall
        clocks, which a starved box reads at 0.4.)"""
        _fit_streamed_sgd(depth=2)  # warmup: compiles happen here
        diagnostics.reset()
        _fit_streamed_sgd(depth=2)

        rep = diagnostics.run_report()
        dev = rep["device"]
        assert dev["dispatches"] >= 8
        assert dev["busy_s"] > 0
        assert dev["utilization"] == pytest.approx(
            dev["busy_s"] / dev["window_s"], abs=1e-3), dev
        assert dev["idle_s"] == pytest.approx(
            dev["window_s"] - dev["busy_s"], abs=1e-5)
        assert len(dev["idle_gaps"]) <= 3
        # per-program attribution carries the cache's registry names
        assert any(p["busy_s"] > 0 for p in dev["programs"].values())

    def test_device_section_in_run_report_resets(self):
        _fit_streamed_sgd(depth=0, n_blocks=2)
        assert diagnostics.run_report()["device"]["dispatches"] > 0
        diagnostics.reset()
        assert diagnostics.run_report()["device"]["dispatches"] == 0

    def test_depth0_also_accounts_device_time(self):
        # the cache choke point covers the serial path identically
        diagnostics.reset()
        _fit_streamed_sgd(depth=0, n_blocks=3)
        dev = diagnostics.run_report()["device"]
        assert dev["dispatches"] >= 3
        assert dev["busy_s"] > 0


# -- Prometheus text format (obs/serve.py) -------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? (NaN|[-+0-9.e]+)$')


def _assert_valid_prometheus(text):
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            assert re.match(
                r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                r"(counter|gauge|summary)$", line), line
        else:
            assert _SAMPLE_RE.match(line), line


class TestPrometheusText:
    def test_counter_gauge_summary_shapes(self):
        reg = obs.registry()
        reg.counter("unit.count", "a").inc(3)
        reg.gauge("unit.depth").set(2.5)
        h = reg.histogram("unit.lat_s")
        for v in (0.01, 0.02, 0.03):
            h.record(v)
        text = serve.prometheus_text()
        _assert_valid_prometheus(text)
        assert "# TYPE unit_count counter" in text
        assert 'unit_count{tag="a"} 3.0' in text
        assert "# TYPE unit_depth gauge" in text
        assert "# TYPE unit_lat_s summary" in text
        assert 'unit_lat_s{quantile="0.5"}' in text
        assert 'unit_lat_s{quantile="0.99"}' in text
        assert "unit_lat_s_sum" in text
        assert "unit_lat_s_count 3" in text

    def test_label_value_escaping(self):
        """Satellite: Prometheus text-format escaping of label values —
        tag names carrying backslash, double-quote, and newline must
        round-trip per the exposition format's three escapes."""
        reg = obs.registry()
        reg.counter("unit.esc", 'say "hi"\nback\\slash').inc()
        text = serve.prometheus_text()
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("unit_esc{"))
        assert '\\"hi\\"' in line
        assert "\\n" in line and "\n" not in line[:-1].replace(
            "\\n", "")
        assert "\\\\slash" in line
        # the raw newline must NOT appear inside the sample line
        assert line == line.strip()
        _assert_valid_prometheus(text)

    def test_name_mangling(self):
        reg = obs.registry()
        reg.counter("1weird.name-x").inc()
        text = serve.prometheus_text()
        assert "# TYPE _1weird_name_x counter" in text

    def test_empty_histogram_quantiles_are_nan(self):
        obs.registry().histogram("unit.empty_s")
        text = serve.prometheus_text()
        assert 'unit_empty_s{quantile="0.5"} NaN' in text
        _assert_valid_prometheus(text)


# -- the live endpoint ---------------------------------------------------

def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
            return resp.status, dict(resp.headers), resp.read().decode()
    except urllib.error.HTTPError as e:  # 4xx/5xx still carry a body
        return e.code, dict(e.headers), e.read().decode()


class TestMetricsEndpoint:
    def test_scrape_during_fit_serves_device_and_block_quantiles(self):
        """Acceptance criterion: curl localhost:$PORT/metrics during a
        fit returns valid Prometheus text including device_busy_s and
        pipeline_block_s quantiles from a supervisor-registered
        endpoint."""
        srv = serve.start(port=0)
        assert srv is not None and srv.port > 0
        _fit_streamed_sgd(depth=2, n_blocks=4)  # warm compiles

        scraped = {}

        def scrape_mid_fit():
            scraped["mid"] = _get(srv.port, "/metrics")

        t = threading.Thread(target=scrape_mid_fit)
        gen = _sgd_blocks(6)

        def blocks_with_scrape():
            for i, item in enumerate(gen):
                if i == 3:
                    t.start()
                yield item

        from dask_ml_tpu.linear_model import SGDClassifier

        stream_partial_fit(SGDClassifier(random_state=0),
                           blocks_with_scrape(), depth=2,
                           fit_kwargs={"classes": np.array([0, 1])})
        t.join(timeout=10)
        status, headers, text = scraped["mid"]
        assert status == 200
        assert "version=0.0.4" in headers["Content-Type"]
        _assert_valid_prometheus(text)
        assert "# TYPE device_busy_s summary" in text
        assert re.search(r'device_busy_s\{[^}]*quantile="0\.99"\}', text)
        assert "# TYPE pipeline_block_s summary" in text
        assert re.search(r'pipeline_block_s\{quantile="0\.5"\}', text)
        assert "device_dispatches" in text

        hb = supervisor.lookup(serve.METRICS_THREAD_NAME)
        assert hb is not None and hb.verdict() == "healthy"
        assert hb.beats >= 1  # one beat per request served

    def test_healthz_ok_and_degraded(self):
        srv = serve.start(port=0)
        status, _, body = _get(srv.port, "/healthz")
        assert status == 200
        verdict = json.loads(body)
        assert verdict["ok"] is True
        assert serve.METRICS_THREAD_NAME not in verdict["dead"]

        # a supervised unit whose thread died flips the probe to 503
        dead_thread = threading.Thread(target=lambda: None)
        dead_thread.start()
        dead_thread.join()
        hb = supervisor.register("unit-under-test", "pipeline",
                                 thread=dead_thread)
        try:
            status, _, body = _get(srv.port, "/healthz")
            assert status == 503
            assert "unit-under-test" in json.loads(body)["dead"]
        finally:
            hb.retire()
        status, _, _ = _get(srv.port, "/healthz")
        assert status == 200

    def test_unknown_path_404(self):
        srv = serve.start(port=0)
        status, _, body = _get(srv.port, "/nope")
        assert status == 404
        assert "/metrics, /healthz or /readyz" in body

    def test_keep_alive_client_cannot_wedge_the_endpoint(self):
        """The endpoint is ONE serving thread: a client holding its
        connection open between scrapes (a real Prometheus scraper's
        default) must not block other clients — responses close the
        connection, and a silent connection times out instead of
        parking the serve loop forever."""
        import http.client
        import socket

        srv = serve.start(port=0)
        # a keep-alive scraper: the server must answer and CLOSE
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=10)
        try:
            conn.request("GET", "/metrics",
                         headers={"Connection": "keep-alive"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.headers.get("Connection") == "close"
            resp.read()
            # while a second raw socket sits connected and SILENT, the
            # endpoint must still serve others (the silent socket is
            # bounded by the handler's socket timeout, not forever)
            quiet = socket.create_connection(("127.0.0.1", srv.port),
                                             timeout=10)
            try:
                status, _, _ = _get(srv.port, "/healthz")
                assert status == 200
            finally:
                quiet.close()
        finally:
            conn.close()

    def test_start_is_idempotent_and_stop_retires(self):
        srv = serve.start(port=0)
        assert serve.start(port=0) is srv
        assert serve.active() is srv
        port = srv.port
        serve.stop()
        assert serve.active() is None
        assert supervisor.lookup(serve.METRICS_THREAD_NAME) is None
        with pytest.raises(OSError):
            _get(port, "/metrics")

    def test_reset_zeroes_books_but_keeps_endpoint_serving(self):
        """Satellite: diagnostics.reset() clears the device books and
        the endpoint survives cleanly — re-registered, zeroed, still
        serving."""
        srv = serve.start(port=0)
        _fit_streamed_sgd(depth=0, n_blocks=2)
        _, _, before = _get(srv.port, "/metrics")
        assert "device_dispatches" in before
        diagnostics.reset()
        assert serve.active() is srv and srv.running()
        assert supervisor.lookup(serve.METRICS_THREAD_NAME) is not None
        status, _, after = _get(srv.port, "/metrics")
        assert status == 200
        assert "device_dispatches" not in after  # books zeroed
        # and it keeps recording fresh fits after the reset
        _fit_streamed_sgd(depth=0, n_blocks=2)
        _, _, again = _get(srv.port, "/metrics")
        assert "device_dispatches" in again

    def test_port_knob_strict_parse(self, monkeypatch):
        monkeypatch.setenv(serve.METRICS_PORT_ENV, "")
        assert serve.resolve_port() is None
        monkeypatch.setenv(serve.METRICS_PORT_ENV, "8081")
        assert serve.resolve_port() == 8081
        monkeypatch.setenv(serve.METRICS_PORT_ENV, "http")
        with pytest.raises(ValueError, match="integer port"):
            serve.resolve_port()
        with pytest.raises(ValueError, match="0..65535"):
            serve.resolve_port(70000)

    def test_env_arming_fail_soft_on_taken_port(self, monkeypatch):
        srv = serve.start(port=0)
        # a second process-level arm on the SAME port must warn and
        # continue, not raise (the fit matters more than its scrape)
        monkeypatch.setenv(serve.METRICS_PORT_ENV, str(srv.port))
        serve.stop()  # clear _ACTIVE so start_from_env truly binds
        blocker = serve.MetricsServer(srv.port)  # hold the port, no start
        try:
            assert serve.start_from_env() is None
        finally:
            blocker._server.server_close()

    def test_endpoint_thread_name_is_the_host_only_literal(self):
        from dask_ml_tpu.analysis.rules._spmd import (
            BLESSED_COMPILE_THREADS, HOST_ONLY_THREAD_NAMES)

        srv = serve.start(port=0)
        assert srv._thread.name == serve.METRICS_THREAD_NAME
        assert serve.METRICS_THREAD_NAME in HOST_ONLY_THREAD_NAMES
        # host-only is NOT the compile blessing: the endpoint may never
        # compile, even where the ahead worker may
        assert serve.METRICS_THREAD_NAME not in BLESSED_COMPILE_THREADS

    def test_scrape_is_graftsan_clean(self, sanitizer):
        """Acceptance criterion: the endpoint thread is graftsan-clean —
        zero steady compiles/dispatches from it.  The sanitizer is
        fail-fast: a dispatch from the metrics thread would raise AT
        the violating enqueue inside the handler (a 500, and a
        violation in the report); steady() makes any compile a
        violation too."""
        srv = serve.start(port=0)
        _fit_streamed_sgd(depth=2, n_blocks=3)  # warmup inside scope
        with sanitizer.steady(guard=False):
            _fit_streamed_sgd(depth=2, n_blocks=3)
            status, _, text = _get(srv.port, "/metrics")
            assert status == 200 and "device_busy_s" in text
            status, _, _ = _get(srv.port, "/healthz")
            assert status == 200
        rep = sanitizer.report()
        assert rep["violations"] == []
        assert rep["totals"]["steady_compiles"] == 0


# ---------------------------------------------------------------------------
# roofline (ISSUE 12): peak table, cost capture, the device_report join
# ---------------------------------------------------------------------------

from dask_ml_tpu.obs import roofline  # noqa: E402


class _FakeCompiled:
    def __init__(self, payload):
        self._payload = payload

    def cost_analysis(self):
        if isinstance(self._payload, Exception):
            raise self._payload
        return self._payload


class TestRoofline:
    def test_default_peaks_have_provenance(self):
        cpu = roofline.peaks_for("cpu")
        v5e = roofline.peaks_for("TPU v5 lite")  # as the chip reports it
        assert cpu["source"].startswith("measured")
        assert v5e["source"].startswith("published")
        assert v5e["flops_per_s"] == 1.97e14 and v5e["bytes_per_s"] == 8.19e11
        assert cpu["flops_per_s"] > 0 and cpu["bytes_per_s"] > 0

    def test_unknown_device_kind_has_no_peaks(self):
        # peaks are keyed by device_kind, not platform: a TPU that is not
        # in the table gets nothing — never the v5e row
        for kind in ("TPU v4", "TPU v6 lite", "TPU7x", "tpu", "quantum",
                     None):
            assert roofline.peaks_for(kind) is None, kind

    def test_env_override_and_reset(self, monkeypatch):
        monkeypatch.setenv(roofline.PEAKS_ENV,
                           "cpu:flops=2e11,bytes=3e10;xpu:flops=1,bytes=2")
        roofline.reset_cache()
        try:
            cpu = roofline.peaks_for("cpu")
            assert cpu == {"flops_per_s": 2e11, "bytes_per_s": 3e10,
                           "source": "env"}
            assert roofline.peaks_for("xpu")["source"] == "env"
        finally:
            monkeypatch.delenv(roofline.PEAKS_ENV)
            roofline.reset_cache()

    @pytest.mark.parametrize("raw", [
        "cpu", "cpu:flops=1", "cpu:flops=1,bytes=x",
        "cpu:flops=0,bytes=1", "cpu:flops=1,watts=2",
    ])
    def test_malformed_env_raises(self, raw):
        with pytest.raises(ValueError):
            roofline.parse_peaks(raw)

    def test_attribution_memory_bound_equals_bandwidth_fraction(self):
        peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0,
                 "source": "test"}
        att = roofline.attribution(1.0, 10.0, 2.0, peaks)
        # memory-bound: bound = I * peak_bytes, so the fraction equals
        # achieved bytes/s over peak bytes/s (= 5/10)
        assert att["roofline_frac"] == pytest.approx(0.5)
        assert att["achieved_bytes_per_s"] == pytest.approx(5.0)
        assert att["intensity"] == pytest.approx(0.1)

    def test_attribution_compute_bound_and_zero_flop(self):
        peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0,
                 "source": "test"}
        # intensity 100 -> bound = peak_flops
        att = roofline.attribution(1000.0, 10.0, 20.0, peaks)
        assert att["roofline_frac"] == pytest.approx(0.5)
        # pure data movement scores on bandwidth alone
        att0 = roofline.attribution(0.0, 10.0, 1.0, peaks)
        assert att0["roofline_frac"] == pytest.approx(1.0)
        assert att0["intensity"] == pytest.approx(0.0)

    def test_attribution_without_peaks_reports_rates_only(self):
        att = roofline.attribution(10.0, 10.0, 1.0, None)
        assert att["roofline_frac"] is None
        assert att["achieved_flops_per_s"] == pytest.approx(10.0)

    def test_capture_cost_shapes_and_failsoft(self):
        ok = roofline.capture_cost(_FakeCompiled(
            [{"flops": 8.0, "bytes accessed": 4.0,
              "bytes accessedout{}": 2.0}]))
        assert ok == {"flops": 8.0, "bytes": 4.0, "out_bytes": 2.0}
        # dict form (newer jax), raising backends, junk, and XLA's
        # negative "unknown" sentinel all stay fail-soft
        assert roofline.capture_cost(_FakeCompiled(
            {"flops": 1.0, "bytes accessed": 1.0}))["flops"] == 1.0
        assert roofline.capture_cost(
            _FakeCompiled(RuntimeError("unsupported"))) is None
        assert roofline.capture_cost(_FakeCompiled([])) is None
        assert roofline.capture_cost(_FakeCompiled(
            [{"flops": -1.0, "bytes accessed": 4.0}])) is None

    def test_cached_dispatch_attributes_flops_in_report_and_registry(self):
        from dask_ml_tpu import programs

        def gemm(a, b):
            return a @ b

        prog = programs.cached_program(gemm, name="rftest.gemm")
        a = np.ones((256, 64), np.float32)
        b = np.ones((64, 32), np.float32)
        cur = scope.cursor()
        prog(a, b)
        prog(a, b)
        rep = scope.device_report(since=cur, settle_s=5.0)
        p = rep["programs"]["rftest.gemm"]
        assert p["flops"] > 0 and p["bytes"] > 0
        assert p["roofline_frac"] is not None and p["roofline_frac"] > 0
        assert rep["roofline"]["peaks"]["source"]
        # the registry carries the same attribution for /metrics
        reg = obs.registry()
        assert reg.counter("device.flops", "rftest.gemm").value > 0
        assert reg.counter("device.bytes", "rftest.gemm").value > 0
        txt = serve.prometheus_text()
        assert "device_flops" in txt and "device_roofline_frac" in txt

    def test_fallback_dispatch_reports_time_without_work(self):
        # an interval tracked WITHOUT cost (the jitted-twin fallback /
        # graftsan hook path) must not invent flops
        t0 = time.perf_counter()
        scope.track("rftest.nocost", t0, [_Leaf(ready=True)])
        rep = scope.device_report(settle_s=1.0)
        p = rep["programs"]["rftest.nocost"]
        assert "flops" not in p and "roofline_frac" not in p

    def test_malformed_peaks_is_failsoft_on_the_sweep_path(self,
                                                           monkeypatch):
        # a typo'd DASK_ML_TPU_PEAKS must not kill the sampler or a
        # dispatch: the sweep's lookup degrades to no-peaks (warn once),
        # while the strict parse still raises on the loud surfaces
        monkeypatch.setenv(roofline.PEAKS_ENV, "tpu:flops=4.9e13")
        roofline.reset_cache()
        try:
            with pytest.raises(ValueError):
                roofline.peaks_for("cpu")
            assert roofline.try_peaks_for("cpu") is None
            t0 = time.perf_counter()
            scope.track("rftest.badpeaks", t0, [_Leaf(ready=True)],
                        cost={"flops": 8.0, "bytes": 4.0})
            scope.sweep()  # must not raise
            rep_programs = {}
            # device_report is a loud surface: it raises on the bad knob
            with pytest.raises(ValueError):
                scope.device_report(settle_s=1.0)
        finally:
            monkeypatch.delenv(roofline.PEAKS_ENV)
            roofline.reset_cache()
