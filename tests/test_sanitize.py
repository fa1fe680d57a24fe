"""graftsan: the runtime SPMD sanitizer gates itself (tier-1).

Three layers, mirroring tests/test_graftlint.py's structure for the
static half:

* detector semantics on synthetic programs — compile attribution,
  steady-phase compile violations, off-thread dispatch fail-fast,
  blessed-thread allowance, the implicit-transfer guard and its
  AllowSite escapes;
* the committed per-workload contract — the smoke suite
  (``dask_ml_tpu/sanitize/smoke.py``) must run clean against
  ``tools/sanitize_baseline.json`` (steady-state streamed fits compile
  ZERO new programs at prefetch depth 0 and 2, dispatch from one
  thread, and perform zero unallowed transfers), and the ratchet must
  fail on a deliberately-introduced steady-state compile, on new/stale
  workloads, and on count regressions;
* the static↔runtime bridge — every AllowSite citation must resolve to
  a suppressed finding in the committed graftlint baseline, so a dead
  suppression cannot keep a live runtime escape.
"""

import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import sanitize
from dask_ml_tpu.sanitize import baseline as san_baseline
from dask_ml_tpu.sanitize.smoke import (
    WORKLOADS,
    metrics_from,
    run_smoke,
    run_workload,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAN_BASELINE = os.path.join(REPO, "tools", "sanitize_baseline.json")
LINT_BASELINE = os.path.join(REPO, "tools", "graftlint_baseline.json")


def _fresh_jit():
    """A jitted callable no other test can have warmed: compiling it is
    guaranteed to emit a backend-compile event."""
    return jax.jit(lambda v: v * 2.0 + 1.0)


#: module-level so a re-run cannot trip the duplicate-site guard
_TEST_SITE = sanitize.AllowSite(
    "test-escape", rule="host-sync-loop", cites="0" * 16,
    note="unit-test fixture site")


# ---------------------------------------------------------------------------
# detector semantics
# ---------------------------------------------------------------------------

class TestCompileDetector:
    def test_compile_counted_and_attributed(self, sanitizer):
        f = _fresh_jit()
        x = jnp.ones(4)
        with sanitize.region("unit.compile"):
            f(x)
        rep = sanitizer.report()
        assert rep["regions"]["unit.compile"]["compiles"] >= 1
        assert rep["regions"]["unit.compile"]["dispatches"] >= 1
        assert not rep["violations"]

    def test_warm_call_compiles_nothing(self, sanitizer):
        f = _fresh_jit()
        x = jnp.ones(4)
        f(x)
        before = sanitizer.report()["totals"]["compiles"]
        f(x)
        assert sanitizer.report()["totals"]["compiles"] == before

    def test_steady_state_compile_is_a_violation(self, sanitizer):
        """The acceptance regression test: a deliberately-introduced new
        steady-state compile must fail the gate."""
        f = _fresh_jit()
        x = jnp.ones(4)
        f(x)  # warmup
        with sanitizer.steady(guard=False):
            f(jnp.ones(5))  # new shape -> new program IN STEADY
        rep = sanitizer.report()
        assert any(v["kind"] == "steady-state-compile"
                   for v in rep["violations"])
        with pytest.raises(sanitize.CompileViolation):
            sanitizer.assert_clean()
        # and the same run fails the baseline ratchet as a hard invariant
        m = metrics_from(sanitizer)
        assert m["steady_compiles"] >= 1
        delta = san_baseline.compare(
            {"workloads": {"wl": {**m, "steady_compiles": 0,
                                  "violations": 0}}}, {"wl": m})
        assert any("steady_compiles" in v for v in delta["violations"])

    def test_off_thread_compile_fails_fast_in_that_thread(self):
        errs = []
        with sanitize.sanitize(label="t") as s:
            def rogue():
                try:
                    _fresh_jit()(jnp.ones(3))
                except sanitize.CompileViolation as e:
                    errs.append(e)
                except sanitize.DispatchViolation as e:
                    errs.append(e)
            t = threading.Thread(target=rogue, name="rogue-compiler")
            t.start()
            t.join()
        assert errs, "off-thread compile/dispatch must raise in the worker"
        assert s.report()["violations"]


class TestDispatchDetector:
    def test_second_thread_dispatch_fails_fast(self):
        f = _fresh_jit()
        x = jnp.ones(4)
        f(x)  # warm OUTSIDE the scope: the rogue dispatch is compile-free
        errs = []
        with sanitize.sanitize(label="t") as s:
            f(x)

            def rogue():
                try:
                    f(x)
                except sanitize.DispatchViolation as e:
                    errs.append(e)

            t = threading.Thread(target=rogue, name="rogue-dispatcher")
            t.start()
            t.join()
        assert len(errs) == 1
        assert any(v["kind"] == "off-thread-dispatch"
                   for v in s.report()["violations"])

    def test_blessed_compile_thread_is_allowed(self):
        f = _fresh_jit()
        x = jnp.ones(4)
        ok = []
        with sanitize.sanitize(label="t") as s:
            def warmer():
                ok.append(f(x) is not None)

            t = threading.Thread(
                target=warmer, name="dask-ml-tpu-compile-ahead")
            t.start()
            t.join()
        assert ok == [True]
        assert not s.report()["violations"]
        assert "dask-ml-tpu-compile-ahead" in s.report()["dispatch_threads"]
        # PR-8 attribution: the blessed thread's compile lands in the
        # separately-ratcheted ahead counters, not in "compiles"
        totals = s.report()["totals"]
        assert totals["ahead_compiles"] >= 1
        assert totals["compiles"] == 0

    def test_blessed_steady_compile_attributed_not_violating(self):
        """A steady-phase compile on the blessed compile-ahead thread is
        that thread's job: counted in steady_ahead_compiles (a ratchet
        ceiling), never a steady-state-compile violation — while the
        same compile on the main thread (sibling test below) stays a
        hard zero."""
        f = _fresh_jit()
        x = jnp.ones(6)
        with sanitize.sanitize(label="t") as s:
            with s.steady(guard=False):
                t = threading.Thread(
                    target=lambda: f(x), name="dask-ml-tpu-compile-ahead")
                t.start()
                t.join()
        rep = s.report()
        assert not rep["violations"]
        assert rep["totals"]["steady_compiles"] == 0
        assert rep["totals"]["steady_ahead_compiles"] >= 1

    def test_prefetch_worker_name_is_not_blessed(self):
        """The §8 contract at runtime: the staging worker's thread name
        dispatching a program IS the deadlock class, caught at the
        violating enqueue."""
        from dask_ml_tpu.pipeline.core import PREFETCH_THREAD_NAME

        f = _fresh_jit()
        x = jnp.ones(4)
        f(x)
        errs = []
        with sanitize.sanitize(label="t"):
            def bad_worker():
                try:
                    f(x)
                except (sanitize.DispatchViolation,
                        sanitize.CompileViolation) as e:
                    errs.append(e)

            t = threading.Thread(target=bad_worker,
                                 name=PREFETCH_THREAD_NAME)
            t.start()
            t.join()
        assert errs

    def test_nested_sanitize_raises(self, sanitizer):
        with pytest.raises(RuntimeError, match="already active"):
            with sanitize.sanitize(label="inner"):
                pass  # pragma: no cover


class TestTransferDetector:
    def test_steady_guard_blocks_implicit_transfer(self, sanitizer):
        with sanitizer.steady():
            with pytest.raises(Exception, match="Disallowed"):
                jnp.zeros(3)  # scalar-const materialization: implicit

    def test_explicit_staging_put_stays_legal(self, sanitizer):
        # the §8 staging contract: jnp.asarray of host numpy is a put
        with sanitizer.steady():
            out = jnp.asarray(np.ones(3, np.float32))
        assert out.shape == (3,)

    def test_allow_site_escape_and_count(self, sanitizer):
        site = _TEST_SITE
        with sanitizer.steady():
            with site.allow():
                jnp.zeros(3)  # implicit, but explicitly allowed here
        assert sanitizer.report()["allow_sites"]["test-escape"] == 1

    def test_d2h_sync_counter(self, sanitizer):
        x = jnp.ones(3) + 0.0
        with sanitize.region("unit.d2h"):
            float(jnp.sum(x))
        assert sanitizer.report()["regions"]["unit.d2h"]["d2h_syncs"] >= 1

    def test_unshard_counted_at_definition(self, sanitizer):
        """The API-boundary fetch is instrumented IN unshard itself —
        call sites that bound the name at import time (most of the
        package) must still count."""
        from dask_ml_tpu.core.sharded import unshard

        x = jnp.ones(8) + 0.0
        with sanitize.region("unit.unshard"):
            out = unshard(x)
        assert out.shape == (8,)
        assert sanitizer.report()["regions"]["unit.unshard"][
            "d2h_syncs"] >= 1

    def test_steady_guard_false_disarms_step_guard(self, sanitizer):
        """steady(guard=False) must govern the estimator-internal
        step_guard() calls too — the per-steady choice, not the
        constructor default."""
        with sanitizer.steady(guard=False):
            with sanitize.step_guard():
                jnp.zeros(3)  # implicit transfer: must NOT raise

    def test_ambient_skips_when_scoped_sanitizer_active(self, sanitizer):
        # atomic-or-skip: the ambient env wrapper must never crash a
        # fit on the no-nesting rule when an explicit scope is open
        with sanitize.ambient("ambient:race") as a:
            assert a is None
        assert sanitize.active_sanitizer() is sanitizer


# ---------------------------------------------------------------------------
# the committed per-workload contract (the tier-1 gate)
# ---------------------------------------------------------------------------

def _gated_workloads():
    """Every workload the suite runs or the committed snapshot names
    (one in either alone is a new or a stale entry, and fails)."""
    committed = san_baseline.load(SAN_BASELINE)["workloads"]
    return sorted(set(WORKLOADS) | set(committed))


class TestWorkloadGate:
    @pytest.fixture(scope="class")
    def smoke_results(self):
        """ONE full smoke run shared by the gate tests (the suite is
        the expensive part; every assertion reads the same results)."""
        return run_smoke()

    def test_streamed_fits_steady_clean_depth_0_and_2(self, smoke_results):
        """The acceptance criterion: steady-state SGD / MiniBatchKMeans /
        IncrementalPCA streamed fits compile ZERO post-warmup programs
        and perform zero unallowed transfers at prefetch depth 0 AND 2,
        dispatching from a single thread throughout."""
        for wl in ("sgd_stream_d0", "sgd_stream_d2", "mbk_stream_d0",
                   "mbk_stream_d2", "ipca_stream_d0", "ipca_stream_d2"):
            m = smoke_results[wl]
            assert not m.get("error"), f"{wl}: {m.get('error')}"
            assert m["steady_compiles"] == 0, wl
            assert m["violations"] == 0, wl
            assert m["transfer_errors"] == 0, wl
            assert m["steady_d2h_syncs"] == 0, wl
            assert len(m["dispatch_threads"]) == 1, wl

    def test_prefetch_worker_never_dispatches(self, smoke_results):
        from dask_ml_tpu.pipeline.core import PREFETCH_THREAD_NAME

        for wl in ("sgd_stream_d2", "mbk_stream_d2", "ipca_stream_d2"):
            assert PREFETCH_THREAD_NAME not in \
                smoke_results[wl]["dispatch_threads"], wl

    @pytest.mark.parametrize("workload", _gated_workloads())
    def test_committed_baseline_matches(self, smoke_results, workload):
        """The ratchet gate, a case a workload so that a drifted count
        is named by the workload it drifted in: the run must be clean
        against the COMMITTED snapshot — new compiles/transfers fail,
        a workload the snapshot lacks fails, a stale entry fails."""
        snap = san_baseline.load(SAN_BASELINE)

        def only(table):
            return {k: v for k, v in table.items() if k == workload}

        delta = san_baseline.compare(
            {**snap, "workloads": only(snap["workloads"])},
            only(smoke_results))
        assert san_baseline.is_clean(delta), delta

    def test_whole_array_fits_compile_free_on_refit(self, smoke_results):
        for wl in ("kmeans_fit", "kmeans_fit_ckpt", "mbk_fit", "glm_fit"):
            m = smoke_results[wl]
            assert not m.get("error"), f"{wl}: {m.get('error')}"
            assert m["steady_compiles"] == 0, wl
            assert m["violations"] == 0, wl

    def test_allow_sites_exercised_not_vacuous(self, smoke_results):
        """The boundary-sync ratchet must have teeth: the checkpointed
        Lloyd and MBK epoch workloads pass their AllowSites a NONZERO
        number of times, so a regression that syncs more often fails
        the committed allow-site ceiling rather than sailing through an
        all-empty table."""
        assert smoke_results["kmeans_fit_ckpt"]["allow_sites"].get(
            "kmeans-segment-sync", 0) >= 1
        assert smoke_results["mbk_fit"]["allow_sites"].get(
            "mbk-epoch-sync", 0) >= 1


class TestFaultInjection:
    def test_worker_ingest_retry_does_not_double_count(self, tmp_path, rng):
        """An absorbed transient ingest fault (retried INSIDE the
        prefetch worker) must not mint compiles or violations: the
        retry re-reads host bytes, it never re-dispatches."""
        from dask_ml_tpu import io as dio
        from dask_ml_tpu.linear_model import SGDRegressor
        from dask_ml_tpu.pipeline import stream_partial_fit
        from dask_ml_tpu.resilience.testing import FaultPlan, fault_plan

        X = rng.normal(size=(400, 5)).astype(np.float32)
        p = tmp_path / "r.bin"
        X.tofile(p)

        def blocks(retries=0):
            for b in dio.stream_binary_blocks(str(p), 100, 5,
                                              retries=retries):
                yield b[:, :4], b[:, 4]

        model = SGDRegressor(random_state=0)
        with sanitize.sanitize(label="fault") as s:
            stream_partial_fit(model, blocks(), depth=2)  # warmup
            plan = FaultPlan()
            plan.inject("ingest", at_call=2, times=1)
            with s.steady(), fault_plan(plan):
                stream_partial_fit(model, blocks(retries=2), depth=2)
        assert plan.fired["ingest"] == 1
        m = metrics_from(s)
        assert m["steady_compiles"] == 0
        assert m["violations"] == 0
        assert m["transfer_errors"] == 0

    def test_step_fault_retry_does_not_recompile(self, rng):
        """A failed step retried at the stream level re-dispatches the
        SAME program: steady-state compile count stays zero across the
        retry (the 'retries must not double-count compiles' contract)."""
        from dask_ml_tpu.linear_model import SGDRegressor
        from dask_ml_tpu.pipeline import stream_partial_fit
        from dask_ml_tpu.resilience.testing import (
            FaultInjected, FaultPlan, fault_plan,
        )

        def blocks():
            r = np.random.RandomState(3)
            for _ in range(4):
                X = r.normal(size=(64, 4)).astype(np.float32)
                yield X, X[:, 0]

        model = SGDRegressor(random_state=0)
        with sanitize.sanitize(label="stepfault") as s:
            stream_partial_fit(model, blocks(), depth=0)  # warmup
            plan = FaultPlan()
            plan.inject("step", at_call=2, times=1)
            with s.steady():
                with fault_plan(plan):
                    with pytest.raises(FaultInjected):
                        stream_partial_fit(model, blocks(), depth=0)
                # the retry: same shapes, same programs — no compile
                stream_partial_fit(model, blocks(), depth=0)
        m = metrics_from(s)
        assert m["steady_compiles"] == 0
        assert m["violations"] == 0


# ---------------------------------------------------------------------------
# baseline ratchet semantics (mirrors test_graftlint's TestBaseline)
# ---------------------------------------------------------------------------

def _clean_metrics(**over):
    m = {"warmup_compiles": 5, "steady_compiles": 0, "steady_d2h_syncs": 2,
         "violations": 0, "transfer_errors": 0,
         "allow_sites": {"site-a": 3}, "dispatch_threads": ["MainThread"]}
    m.update(over)
    return m


class TestBaselineRatchet:
    def test_round_trip_and_clean_compare(self, tmp_path):
        results = {"wl": _clean_metrics()}
        path = str(tmp_path / "san.json")
        san_baseline.write(path, san_baseline.emit(results))
        snap = san_baseline.load(path)
        assert snap["tool"] == "graftsan"
        delta = san_baseline.compare(snap, results)
        assert san_baseline.is_clean(delta)

    def test_new_workload_fails(self):
        snap = {"workloads": {"wl": _clean_metrics()}}
        delta = san_baseline.compare(
            snap, {"wl": _clean_metrics(), "extra": _clean_metrics()})
        assert delta["new"] == ["extra"]

    def test_stale_entry_fails(self):
        """The committed snapshot must always match the suite: an entry
        whose workload no longer runs is itself a gate failure."""
        snap = {"workloads": {"wl": _clean_metrics(),
                              "gone": _clean_metrics()}}
        delta = san_baseline.compare(snap, {"wl": _clean_metrics()})
        assert delta["stale"] == ["gone"]
        assert not san_baseline.is_clean(delta)

    def test_new_compiles_ratchet(self):
        snap = {"workloads": {"wl": _clean_metrics()}}
        delta = san_baseline.compare(
            snap, {"wl": _clean_metrics(warmup_compiles=6)})
        assert any("warmup_compiles" in r for r in delta["regressions"])

    def test_fewer_compiles_pass(self):
        # ceilings, not identities: a warm jit cache legitimately
        # observes fewer compiles than the cold rebaseline run
        snap = {"workloads": {"wl": _clean_metrics()}}
        delta = san_baseline.compare(
            snap, {"wl": _clean_metrics(warmup_compiles=0)})
        assert san_baseline.is_clean(delta)

    def test_new_transfers_ratchet(self):
        snap = {"workloads": {"wl": _clean_metrics()}}
        delta = san_baseline.compare(
            snap, {"wl": _clean_metrics(steady_d2h_syncs=9)})
        assert any("steady_d2h_syncs" in r for r in delta["regressions"])

    def test_allow_site_count_ratchet(self):
        snap = {"workloads": {"wl": _clean_metrics()}}
        delta = san_baseline.compare(
            snap, {"wl": _clean_metrics(allow_sites={"site-a": 4})})
        assert any("site-a" in r for r in delta["regressions"])
        delta2 = san_baseline.compare(
            snap, {"wl": _clean_metrics(allow_sites={"rogue": 1,
                                                     "site-a": 3})})
        assert any("rogue" in r for r in delta2["regressions"])

    def test_snapshot_cannot_grandfather_violations(self):
        snap = {"workloads": {"wl": _clean_metrics(steady_compiles=2)}}
        delta = san_baseline.compare(snap, {"wl": _clean_metrics()})
        assert any("grandfather" in v for v in delta["violations"])

    def test_partial_run_checks_invariants_only(self):
        snap = {"workloads": {"wl": _clean_metrics(),
                              "other": _clean_metrics()}}
        delta = san_baseline.compare(
            snap, {"wl": _clean_metrics(warmup_compiles=99)}, partial=True)
        assert san_baseline.is_clean(delta)
        delta2 = san_baseline.compare(
            snap, {"wl": _clean_metrics(steady_compiles=1)}, partial=True)
        assert not san_baseline.is_clean(delta2)

    def test_newer_version_refused(self, tmp_path):
        path = str(tmp_path / "future.json")
        with open(path, "w") as fh:
            json.dump({"version": 99, "workloads": {}}, fh)
        with pytest.raises(ValueError, match="newer"):
            san_baseline.load(path)

    def test_malformed_refused(self, tmp_path):
        path = str(tmp_path / "bad.json")
        with open(path, "w") as fh:
            json.dump({"version": 1}, fh)
        with pytest.raises(ValueError, match="malformed"):
            san_baseline.load(path)


# ---------------------------------------------------------------------------
# the static <-> runtime bridge
# ---------------------------------------------------------------------------

class TestAllowSiteCitations:
    def test_every_site_cites_a_live_suppression(self):
        """Each runtime allow-site must cite a suppressed finding in the
        COMMITTED graftlint baseline, same rule — a deleted suppression
        invalidates its runtime escape, and this test is what notices."""
        import dask_ml_tpu  # noqa: F401  (registers every module's sites)
        import dask_ml_tpu.cluster.spectral  # noqa: F401  (lazy module)

        with open(LINT_BASELINE) as fh:
            snap = json.load(fh)
        suppressed = {
            e["fingerprint"]: e["rule"]
            for e in snap["findings"] if e["suppressed"]
        }
        sites = sanitize.registered_sites()
        # every production module's sites are registered by the imports
        # (search-packed-scores retired with ISSUE 13: the cohort
        # refactor removed the static host-sync-loop finding it
        # bridged — float() of an already-fetched numpy vector)
        assert {"kmeans-segment-sync", "mbk-epoch-sync",
                "spectral-ritz-sync", "ensemble-epoch-sync"} <= set(sites)
        for site in sites.values():
            if site.site_id.startswith("test-"):
                continue  # unit-test fixtures register throwaway sites
            for fp in site.cites:
                assert fp in suppressed, (
                    f"AllowSite {site.site_id!r} cites {fp} which is not "
                    f"a suppressed finding in tools/graftlint_baseline"
                    f".json — the static suppression it runtime-verifies "
                    f"is gone; delete or re-cite the site")
                assert suppressed[fp] == site.rule, site.site_id

    def test_suppression_budget(self):
        """The PR-6 triage target: ≤ 13 inline suppression comments.
        The runtime sanitizer proved the truncated_svd streaming path
        host-only, so its four suppressions became a named host tail
        (count 8); PR-8 added exactly ONE — the ``jit-outside-cache``
        rule's sanctioned escape at the program cache's own internal
        ``jax.jit`` wrap (programs/cache.py), the single place a raw
        jit must exist (count 9).  PR-9 added TWO, both runtime-
        verified by the new machinery itself: the blessed compile-ahead
        thread's ``thread-dispatch`` escape (programs/ahead.py — its
        supervisor/flight bookkeeping is host-only but dynamically
        dispatched, and graftsan's dispatch detector plus the
        ahead-crash drill verify the thread never dispatches) and the
        JSONL sink's shutdown ``swallowed-fault`` escape
        (obs/export.py — the sink already warned once when it was
        dropped; the exporter-ENOSPC drill pins that contract) — count
        11.  ISSUE 12 added FIVE, all ``donation-miss`` justifications
        for the deliberate non-donations (the gemm-output-smaller
        class: kmeans.assign, sgd.eval_loss, naive_bayes
        class_moments, serve margins + lane_margins) — each
        runtime-verified by an aliasing regression test asserting the
        undonated buffers really survive — count 16.  ISSUE 13
        REMOVED one: the packed-scores ``host-sync-loop`` suppression
        (and its ``search-packed-scores`` AllowSite twin) retired when
        the cohort refactor made the finding vanish — the per-model
        ``float()`` reads an already-fetched numpy vector (count 15).
        ISSUE 15 added ONE: the data-reader spawn's
        ``thread-dispatch`` escape (data/readers.py) — the readers now
        record graftpath ``data.parse``/``data.fetch`` intervals via
        ``obs.record_span``, a pure-stdlib call the static prover
        cannot resolve cross-module; the ``ingest_parallel`` graftsan
        workload runtime-verifies the contract (any dispatch
        attributed to a reader thread is a hard violation) — count 16.
        ISSUE 18 added SEVEN, all on the graftpilot controller
        (control/pilot.py): the host-only ``dask-ml-tpu-pilot`` thread
        (``thread-dispatch``; it is in ``HOST_ONLY_THREAD_NAMES`` and
        graftsan's dispatch detector would flag any dispatch it made)
        plus its single-owner cycle state (``unguarded-shared-state``;
        written only from the pilot thread itself) — count 23.  PR 19
        added ONE: the fleet-deploy drill's traffic thread
        (resilience/drills.py, ``thread-dispatch``) — it only ENQUEUES
        via ``ModelServer.submit`` and parks on the future; every
        device dispatch stays on the replicas' blessed serve loops,
        runtime-verified by the dispatch detector across the serve
        drills — count 24.  ISSUE 20 added ONE: the lock sanitizer's
        rogue-writer drill thread (sanitize/locks.py,
        ``contract-roster-drift``) — the thread is deliberately OFF
        the ``_spmd`` roster because the drill EXISTS to prove the
        runtime roster check catches an unreviewed package-prefixed
        thread; rostering it would blind the very check it verifies —
        so the count is now 25.  ISSUE 28 added THREE: the k-means||
        programs ``kmeans.init_first``, ``kmeans.row_norms`` and
        ``kmeans.init_scalable`` (cluster/k_means.py,
        ``donation-miss``) — the first's only same-shape input is the
        mask, live in the caller; the second's one input is the table;
        the third's outputs (candidate buffer, weights) are smaller
        than every row-sized input, so XLA reports its donated
        distances unusable — count 28.  ISSUE 32 added TWO: the
        programs ``tsqr.r`` (linalg/tsqr.py) and ``pca.spectrum``
        (decomposition/pca.py), ``donation-miss`` — the first's
        outputs are (d, d) and smaller while its row-sized inputs (the
        table, its mask) stay live in the caller, which refits on them;
        the second takes the (d, d) R, which the fallback path still
        reads, and returns (k, d) and vectors — count 30.  ISSUE 37
        added ONE: the program ``kmeans.tol`` (cluster/k_means.py,
        ``donation-miss``) — its outputs are two scalars (the stopping
        threshold and its anchor's share) and its inputs, the table and
        its mask, stay live in the fit — count 31.  The resident SGD pass
        added ONE: the program ``sgd.labels_check`` (linear_model/_sgd_pass.py,
        ``donation-miss``) — its output is one scalar and its inputs are
        the caller's labels, which every later pass reads — count 32."""
        import subprocess

        out = subprocess.run(
            ["grep", "-rc", "graftlint: disable=", "--include=*.py",
             os.path.join(REPO, "dask_ml_tpu")],
            capture_output=True, text=True)
        total = sum(int(line.rsplit(":", 1)[1])
                    for line in out.stdout.splitlines() if ":" in line)
        # analysis/core.py's docstring EXAMPLE is not a live suppression
        assert total - 1 <= 32
        assert total - 1 == 32, (
            "suppression count moved — update this test AND re-audit "
            "the AllowSite citations")


class TestIpcaFitBoundary:
    def test_uninstrumented_fit_has_no_per_block_sync(self, rng):
        """IncrementalPCA.fit without a checkpoint/watcher must not pay
        the boundary-state device fetch per block — the regression the
        on-device count refactor could have reintroduced through the
        eager ``_fit_state()`` in the on_block hook."""
        from dask_ml_tpu.decomposition import IncrementalPCA

        X = rng.normal(size=(160, 4)).astype(np.float32)
        with sanitize.sanitize(label="ipca_fit") as s:
            IncrementalPCA(n_components=2, batch_size=16).fit(X)
        assert s.report()["totals"]["d2h_syncs"] == 0


class TestHostOnlyPathsStayHostOnly:
    def test_truncated_svd_stream_never_touches_device(self, rng):
        """The de-suppressed truncated_svd streaming path, runtime
        verified: a full streamed fit under an armed sanitizer performs
        ZERO device dispatches, compiles, and transfers — the claim the
        four deleted host-sync-loop suppressions used to assert
        statically is now measured."""
        from dask_ml_tpu.decomposition import TruncatedSVD

        blocks = [rng.normal(size=(50, 8)).astype(np.float32)
                  for _ in range(3)]

        with sanitize.sanitize(label="tsvd_stream") as s:
            with s.steady():  # guard armed for the WHOLE fit
                est = TruncatedSVD(n_components=3, random_state=0)
                est.fit_streamed(lambda: iter(blocks), n_features=8)
        rep = s.report()
        assert rep["totals"]["dispatches"] == 0
        assert rep["totals"]["compiles"] == 0
        assert not rep["violations"]
        assert est.components_.shape == (3, 8)


# ---------------------------------------------------------------------------
# diagnostics + ambient mode + CLI
# ---------------------------------------------------------------------------

class TestDiagnosticsReport:
    def test_live_and_last_report(self):
        from dask_ml_tpu import diagnostics

        with sanitize.sanitize(label="diag") as s:
            _fresh_jit()(jnp.ones(2))
            live = diagnostics.sanitize_report()
            assert live["label"] == "diag"
            assert live["totals"]["compiles"] >= 1
        last = diagnostics.sanitize_report()
        assert last["label"] == "diag"
        assert last["totals"] == s.report()["totals"]

    def test_report_shape(self, sanitizer):
        rep = sanitizer.report()
        assert set(rep) == {"label", "phase", "regions", "totals",
                            "violations", "allow_sites",
                            "dispatch_threads"}


class TestAmbientMode:
    def test_env_knob_wraps_streams(self, monkeypatch, rng):
        from dask_ml_tpu import diagnostics
        from dask_ml_tpu.linear_model import SGDRegressor
        from dask_ml_tpu.pipeline import stream_partial_fit

        monkeypatch.setenv(sanitize.SANITIZE_ENV, "1")
        blocks = [(rng.normal(size=(64, 4)).astype(np.float32),
                   rng.normal(size=64).astype(np.float32))
                  for _ in range(3)]
        stream_partial_fit(SGDRegressor(random_state=0), iter(blocks),
                           depth=2, label="ambient_test")
        rep = diagnostics.sanitize_report()
        assert rep is not None
        assert rep["label"] == "ambient:ambient_test"
        assert rep["totals"]["dispatches"] >= 3

    def test_env_knob_off_by_default(self, monkeypatch):
        monkeypatch.delenv(sanitize.SANITIZE_ENV, raising=False)
        assert not sanitize.enabled_by_env()

    def test_env_knob_strict_values(self, monkeypatch):
        # 'false'/'no'/'OFF' are off, case-insensitive; a typo is a loud
        # error, never silently 'on' (ambient mode suppresses the pjit
        # fastpath — nobody should pay that for a bad value)
        for off in ("false", "no", "OFF", "0"):
            monkeypatch.setenv(sanitize.SANITIZE_ENV, off)
            assert not sanitize.enabled_by_env(), off
        for on in ("1", "ON", "true", "yes"):
            monkeypatch.setenv(sanitize.SANITIZE_ENV, on)
            assert sanitize.enabled_by_env(), on
        monkeypatch.setenv(sanitize.SANITIZE_ENV, "maybe")
        with pytest.raises(ValueError, match="DASK_ML_TPU_SANITIZE"):
            sanitize.enabled_by_env()


class TestCLI:
    def test_list_workloads(self, capsys):
        from dask_ml_tpu.sanitize.cli import main

        assert main(["--list-workloads"]) == 0
        out = capsys.readouterr().out
        for wl in WORKLOADS:
            assert wl in out

    def test_unknown_workload_exits_two(self, capsys):
        from dask_ml_tpu.sanitize.cli import main

        assert main(["--workloads", "nope"]) == 2

    def test_unreadable_baseline_exits_two(self, tmp_path, capsys):
        from dask_ml_tpu.sanitize.cli import main

        rc = main(["--workloads", "sgd_stream_d0",
                   "--baseline", str(tmp_path / "missing.json")])
        assert rc == 2

    def test_run_one_workload_json(self, tmp_path, capsys):
        from dask_ml_tpu.sanitize.cli import main

        rc = main(["--workloads", "sgd_stream_d0", "--format", "json",
                   "--baseline", SAN_BASELINE])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["clean"] is True
        assert "sgd_stream_d0" in payload["workloads"]

    def test_partial_write_baseline_refused(self, tmp_path, capsys):
        """A subset snapshot would shadow the committed full-suite
        baseline (everything unselected reads as new on the next gate):
        usage error, exit 2, nothing written."""
        from dask_ml_tpu.sanitize.cli import main

        path = str(tmp_path / "partial.json")
        rc = main(["--workloads", "sgd_stream_d0",
                   "--write-baseline", path])
        assert rc == 2
        assert not os.path.exists(path)

    def test_full_write_baseline_round_trip(self, tmp_path, capsys):
        from dask_ml_tpu.sanitize.cli import main

        path = str(tmp_path / "full.json")
        rc = main(["--write-baseline", path, "--format", "json"])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["clean"] is True
        assert set(json.load(open(path))["workloads"]) == set(WORKLOADS)

    def test_violating_run_never_writes_baseline(self, tmp_path,
                                                 monkeypatch, capsys):
        """A snapshot may never carry a hard-invariant violation: the
        write is gated BEFORE touching disk, so a bad rebaseline leaves
        the committed file exactly as it was."""
        from dask_ml_tpu.sanitize import smoke
        from dask_ml_tpu.sanitize.cli import main

        bad = {"wl": _clean_metrics(steady_compiles=3)}
        monkeypatch.setattr(smoke, "run_smoke", lambda names=None: bad)
        path = str(tmp_path / "bad.json")
        rc = main(["--write-baseline", path])
        assert rc == 1
        assert not os.path.exists(path)


class TestWorkloadRunner:
    def test_unknown_name_raises(self):
        with pytest.raises(KeyError):
            run_smoke(["nope"])

    def test_workload_error_becomes_metric(self, monkeypatch):
        from dask_ml_tpu.sanitize import smoke

        def boom():
            raise RuntimeError("synthetic workload crash")

        monkeypatch.setitem(smoke.WORKLOADS, "boom", boom)
        m = run_workload("boom")
        assert m["violations"] == 1
        assert "synthetic workload crash" in m["error"]
