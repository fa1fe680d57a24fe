"""Checkpoint/resume subsystem tests (SURVEY.md §5: absent in the
reference; designed in here as the fault-recovery story)."""

import numpy as np
import pytest

from dask_ml_tpu.checkpoint import SearchCheckpoint, load_estimator, save_estimator
from dask_ml_tpu.core import shard_rows, unshard
from dask_ml_tpu.model_selection import (
    HyperbandSearchCV,
    IncrementalSearchCV,
    SuccessiveHalvingSearchCV,
)
from dask_ml_tpu.model_selection.utils_test import LinearFunction


class TestEstimatorSaveLoad:
    def test_kmeans_roundtrip(self, tmp_path, rng):
        from dask_ml_tpu.cluster import KMeans

        X = rng.normal(size=(200, 4)).astype(np.float32)
        X[:100] += 5
        km = KMeans(n_clusters=2, random_state=0).fit(X)
        save_estimator(km, str(tmp_path / "km"))
        restored = load_estimator(str(tmp_path / "km"))
        np.testing.assert_allclose(
            np.asarray(km.cluster_centers_),
            np.asarray(restored.cluster_centers_),
            rtol=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(km.predict(X)), np.asarray(restored.predict(X))
        )
        assert restored.get_params() == km.get_params()

    def test_scaler_roundtrip(self, tmp_path, rng):
        from dask_ml_tpu.preprocessing import StandardScaler

        X = rng.normal(size=(64, 3)).astype(np.float32) * 4 + 2
        sc = StandardScaler().fit(X)
        save_estimator(sc, str(tmp_path / "sc"))
        restored = load_estimator(str(tmp_path / "sc"))
        np.testing.assert_allclose(
            unshard(restored.transform(X)), unshard(sc.transform(X)), rtol=1e-6
        )

    def test_glm_roundtrip(self, tmp_path, rng):
        from dask_ml_tpu.linear_model import LogisticRegression

        X = rng.normal(size=(80, 4)).astype(np.float32)
        y = (X @ rng.normal(size=4) > 0).astype(np.float32)
        lr = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
        save_estimator(lr, str(tmp_path / "lr"))
        restored = load_estimator(str(tmp_path / "lr"))
        np.testing.assert_allclose(
            np.asarray(restored.coef_), np.asarray(lr.coef_), rtol=1e-6
        )
        np.testing.assert_array_equal(
            np.asarray(restored.predict(X)), np.asarray(lr.predict(X))
        )

    def test_sharded_attr_roundtrip(self, tmp_path, rng, mesh):
        # an estimator holding a ShardedRows fitted attr must restore it
        # as a re-sharded array on the active mesh
        from dask_ml_tpu.preprocessing import StandardScaler

        sc = StandardScaler()
        X = rng.normal(size=(30, 2)).astype(np.float32)
        sc.fit(X)
        sc.debug_rows_ = shard_rows(X)
        save_estimator(sc, str(tmp_path / "s"))
        restored = load_estimator(str(tmp_path / "s"))
        from dask_ml_tpu.core.sharded import ShardedRows

        assert isinstance(restored.debug_rows_, ShardedRows)
        np.testing.assert_allclose(unshard(restored.debug_rows_), X)


def _xy(rng, n=64, d=3):
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) > 0).astype(np.float32)
    return X, y


class TestSearchCheckpoint:
    def test_resume_after_crash(self, tmp_path, rng):
        """Kill the search mid-flight; a re-fit resumes from the snapshot
        instead of restarting, and reaches the same result."""
        X, y = _xy(rng)
        path = str(tmp_path / "search.pkl")
        params = {"slope": [0.1, 0.5, 1.0, 2.0]}

        # un-checkpointed reference run
        ref = IncrementalSearchCV(
            LinearFunction(), params, n_initial_parameters="grid",
            max_iter=6, random_state=0,
        ).fit(X, y)

        crashing = IncrementalSearchCV(
            LinearFunction(), params, n_initial_parameters="grid",
            max_iter=6, random_state=0, checkpoint=path,
        )
        calls = {"n": 0}
        orig = type(crashing)._additional_calls

        def boom(self, info):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("simulated preemption")
            return orig(self, info)

        import unittest.mock as mock

        with mock.patch.object(type(crashing), "_additional_calls", boom):
            with pytest.raises(RuntimeError, match="preemption"):
                crashing.fit(X, y)
        assert SearchCheckpoint(path).exists()

        # resumed run: models pick up their partial_fit_calls counts
        resumed = IncrementalSearchCV(
            LinearFunction(), params, n_initial_parameters="grid",
            max_iter=6, random_state=0, checkpoint=path,
        ).fit(X, y)
        assert resumed.best_params_ == ref.best_params_
        assert resumed.best_score_ == ref.best_score_
        # final per-model budgets identical to the uninterrupted run
        ref_calls = {
            i: recs[-1]["partial_fit_calls"]
            for i, recs in ref.model_history_.items()
        }
        res_calls = {
            i: recs[-1]["partial_fit_calls"]
            for i, recs in resumed.model_history_.items()
        }
        assert res_calls == ref_calls
        # snapshot removed on successful completion
        assert not SearchCheckpoint(path).exists()

    def test_sha_policy_state_resumes(self, tmp_path, rng):
        """SHA's _steps/_survivors counters are part of the snapshot: a
        resume must not restart the halving schedule from step 0."""
        X, y = _xy(rng)
        path = str(tmp_path / "sha.pkl")
        kwargs = dict(
            parameters={"slope": [0.1, 0.5, 1.0, 2.0, 3.0, 4.0]},
            n_initial_parameters=6, n_initial_iter=2, max_iter=8,
            random_state=0,
        )
        ref = SuccessiveHalvingSearchCV(LinearFunction(), **kwargs).fit(X, y)

        crashing = SuccessiveHalvingSearchCV(
            LinearFunction(), checkpoint=path, **kwargs
        )
        calls = {"n": 0}
        orig = SuccessiveHalvingSearchCV._additional_calls

        def boom(self, info):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("simulated preemption")
            return orig(self, info)

        import unittest.mock as mock

        with mock.patch.object(SuccessiveHalvingSearchCV, "_additional_calls", boom):
            with pytest.raises(RuntimeError):
                crashing.fit(X, y)

        resumed = SuccessiveHalvingSearchCV(
            LinearFunction(), checkpoint=path, **kwargs
        ).fit(X, y)
        assert resumed.best_params_ == ref.best_params_
        ref_calls = {
            i: recs[-1]["partial_fit_calls"]
            for i, recs in ref.model_history_.items()
        }
        res_calls = {
            i: recs[-1]["partial_fit_calls"]
            for i, recs in resumed.model_history_.items()
        }
        assert res_calls == ref_calls

    def test_hyperband_bracket_checkpoints(self, tmp_path, rng):
        X, y = _xy(rng)
        hb = HyperbandSearchCV(
            LinearFunction(), {"slope": [0.5, 1.0, 2.0]}, max_iter=9,
            random_state=0, checkpoint=str(tmp_path / "hb"),
        ).fit(X, y)
        assert hasattr(hb, "best_params_")
        # all bracket snapshots cleaned up after a successful fit
        assert list((tmp_path / "hb").glob("*.pkl")) == []

    def test_mismatched_config_ignored(self, tmp_path, rng):
        """A snapshot from a DIFFERENT search config must not be loaded."""
        X, y = _xy(rng)
        path = str(tmp_path / "s.pkl")

        crashing = IncrementalSearchCV(
            LinearFunction(), {"slope": [1.0, 2.0]}, n_initial_parameters="grid",
            max_iter=6, random_state=0, checkpoint=path,
        )
        calls = {"n": 0}
        orig = type(crashing)._additional_calls

        def boom(self, info):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("simulated preemption")
            return orig(self, info)

        import unittest.mock as mock

        with mock.patch.object(type(crashing), "_additional_calls", boom):
            with pytest.raises(RuntimeError):
                crashing.fit(X, y)
        assert SearchCheckpoint(path).exists()

        # different max_iter and slope grid: snapshot must be ignored and
        # the fresh run must reflect the NEW parameter space
        fresh = IncrementalSearchCV(
            LinearFunction(), {"slope": [5.0]}, n_initial_parameters="grid",
            max_iter=3, random_state=0, checkpoint=path,
        ).fit(X, y)
        assert fresh.best_params_ == {"slope": 5.0}
        assert max(
            recs[-1]["partial_fit_calls"] for recs in fresh.model_history_.values()
        ) <= 3

    def test_resume_preserves_wall_time_ordering(self, tmp_path, rng):
        """history_ stays chronological across a resume: post-resume records
        must carry elapsed_wall_time >= pre-crash records."""
        X, y = _xy(rng)
        path = str(tmp_path / "s.pkl")
        kwargs = dict(
            parameters={"slope": [0.5, 1.0, 2.0]}, n_initial_parameters="grid",
            max_iter=6, random_state=0, checkpoint=path,
        )
        crashing = IncrementalSearchCV(LinearFunction(), **kwargs)
        calls = {"n": 0}
        orig = type(crashing)._additional_calls

        def boom(self, info):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("simulated preemption")
            return orig(self, info)

        import unittest.mock as mock

        with mock.patch.object(type(crashing), "_additional_calls", boom):
            with pytest.raises(RuntimeError):
                crashing.fit(X, y)

        resumed = IncrementalSearchCV(LinearFunction(), **kwargs).fit(X, y)
        times = [r["elapsed_wall_time"] for r in resumed.history_]
        assert times == sorted(times)
        pf = [r["partial_fit_calls"] for r in resumed.history_]
        # chronological => per-model call counts never decrease in history_
        by_model = {}
        for r in resumed.history_:
            prev = by_model.get(r["model_id"], 0)
            assert r["partial_fit_calls"] >= prev
            by_model[r["model_id"]] = r["partial_fit_calls"]
        assert max(pf) == 6

    def test_completed_run_leaves_no_snapshot(self, tmp_path, rng):
        X, y = _xy(rng)
        path = str(tmp_path / "s.pkl")
        IncrementalSearchCV(
            LinearFunction(), {"slope": [1.0, 2.0]}, n_initial_parameters="grid",
            max_iter=3, random_state=0, checkpoint=path,
        ).fit(X, y)
        assert not SearchCheckpoint(path).exists()


import collections

from dask_ml_tpu.base import TPUEstimator

_NTState = collections.namedtuple("_NTState", ["w", "n"])

#: module-level (pickle-able) namedtuple solver state + carrier estimator
#: for the mesh-shape-change roundtrip below
_SolverNTState = collections.namedtuple("_SolverNTState", ["w", "step"])


class _WithState(TPUEstimator):
    def __init__(self):
        pass


class _SolverEst(TPUEstimator):
    _checkpoint_private_attrs = ("_solver_state",)

    def __init__(self):
        pass


class TestHostConversion:
    def test_namedtuple_fitted_attr_roundtrip(self, tmp_path):
        # Tuple subclasses with positional fields (NamedTuple solver states)
        # must be rebuilt field-wise, not passed a single list argument.
        import jax.numpy as jnp

        from dask_ml_tpu.checkpoint import _from_host, _to_host

        State = _NTState
        s = State(w=jnp.arange(3.0), n=7)
        back = _from_host(_to_host(s))
        assert isinstance(back, State)
        np.testing.assert_allclose(np.asarray(back.w), [0.0, 1.0, 2.0])
        assert back.n == 7

        est = _WithState()
        est.state_ = s
        save_estimator(est, str(tmp_path / "ns"))
        loaded = load_estimator(str(tmp_path / "ns"))
        assert isinstance(loaded.state_, State)
        np.testing.assert_allclose(np.asarray(loaded.state_.w), [0.0, 1.0, 2.0])


class TestFingerprint:
    def test_large_array_params_distinguished(self):
        # numpy truncates reprs of >1000-element arrays; the fingerprint
        # must still tell two different big grids apart.
        from dask_ml_tpu.checkpoint import search_fingerprint

        a = np.zeros(2000)
        b = np.zeros(2000)
        b[1500] = 1.0
        s1 = IncrementalSearchCV(
            LinearFunction(), {"intercept": a}, max_iter=3
        )
        s2 = IncrementalSearchCV(
            LinearFunction(), {"intercept": b}, max_iter=3
        )
        assert search_fingerprint(s1) != search_fingerprint(s2)

    def test_identical_config_same_fingerprint(self):
        from dask_ml_tpu.checkpoint import search_fingerprint

        g = {"intercept": np.linspace(0, 1, 5)}
        s1 = IncrementalSearchCV(LinearFunction(), g, max_iter=3)
        s2 = IncrementalSearchCV(LinearFunction(), dict(g), max_iter=3)
        assert search_fingerprint(s1) == search_fingerprint(s2)


class TestDeviceEstimatorRoundtrips:
    def test_sgd_classifier_roundtrip(self, tmp_path, rng):
        from dask_ml_tpu.linear_model import SGDClassifier

        X = rng.normal(size=(200, 5)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        clf = SGDClassifier(max_iter=30, random_state=0).fit(X, y)
        save_estimator(clf, str(tmp_path / "sgd"))
        back = load_estimator(str(tmp_path / "sgd"))
        np.testing.assert_array_equal(back.predict(X), clf.predict(X))
        np.testing.assert_array_equal(back.classes_, clf.classes_)

    def test_minibatch_kmeans_roundtrip(self, tmp_path, rng):
        from dask_ml_tpu.cluster import MiniBatchKMeans

        X = rng.normal(size=(300, 4)).astype(np.float32)
        mbk = MiniBatchKMeans(n_clusters=3, random_state=0, max_iter=10).fit(X)
        save_estimator(mbk, str(tmp_path / "mbk"))
        back = load_estimator(str(tmp_path / "mbk"))
        np.testing.assert_allclose(
            np.asarray(back.cluster_centers_),
            np.asarray(mbk.cluster_centers_), rtol=1e-6,
        )
        # the restored model keeps STREAMING: counts survived the roundtrip
        back.partial_fit(X[:64])
        assert back.n_steps_ == mbk.n_steps_ + 1


class TestCrashMatrix:
    """Resume from a crash at EVERY point of a
    Hyperband run, including mid-bracket and double-crash — each resume
    must reach the uninterrupted run's exact result.  A single crash
    point (the old test) can miss state that only goes stale deeper
    into the bracket ladder."""

    _kwargs = dict(
        parameters={"slope": [0.1, 0.4, 0.8, 1.2, 2.0, 3.0]},
        max_iter=4, aggressiveness=2, random_state=0,
        sequential_brackets=True,  # deterministic call order: the crash
        # index then hits the same schedule point every run
    )

    def _reference(self, X, y):
        return HyperbandSearchCV(LinearFunction(), **self._kwargs).fit(X, y)

    def _crash_at(self, X, y, path, crash_calls):
        """Run with a bracket-checkpoint dir, raising at each SHA call
        index in ``crash_calls`` (consumed in order), resuming in
        between.  Hyperband delegates rounds to per-bracket
        SuccessiveHalvingSearchCV instances, so the crash hook is SHA's
        ``_additional_calls``."""
        import os
        import unittest.mock as mock

        orig = SuccessiveHalvingSearchCV._additional_calls
        for k in crash_calls:
            calls = {"n": 0}

            def boom(self, info, _k=k, _calls=calls):
                _calls["n"] += 1
                if _calls["n"] == _k:
                    raise RuntimeError("simulated preemption")
                return orig(self, info)

            hb = HyperbandSearchCV(
                LinearFunction(), checkpoint=path, **self._kwargs
            )
            with mock.patch.object(
                    SuccessiveHalvingSearchCV, "_additional_calls", boom):
                with pytest.raises(RuntimeError, match="preemption"):
                    hb.fit(X, y)
            # at least one bracket snapshot survives the crash
            assert os.path.isdir(path) and os.listdir(path), path
        resumed = HyperbandSearchCV(
            LinearFunction(), checkpoint=path, **self._kwargs
        ).fit(X, y)
        return resumed

    def _count_calls(self, X, y):
        """Total SHA _additional_calls invocations of a full run."""
        import unittest.mock as mock

        orig = SuccessiveHalvingSearchCV._additional_calls
        counter = {"n": 0}

        def counting(self, info):
            counter["n"] += 1
            return orig(self, info)

        with mock.patch.object(
                SuccessiveHalvingSearchCV, "_additional_calls", counting):
            HyperbandSearchCV(LinearFunction(), **self._kwargs).fit(X, y)
        return counter["n"]

    def test_crash_matrix_every_point(self, tmp_path, rng):
        """Crash at EVERY schedule point the run actually has."""
        X = rng.normal(size=(120, 3)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        ref = self._reference(X, y)
        total = self._count_calls(X, y)
        assert total >= 2, "schedule too short to be a matrix"
        ref_calls = {i: r[-1]["partial_fit_calls"]
                     for i, r in ref.model_history_.items()}
        import os

        for k in range(1, total + 1):
            path = str(tmp_path / f"hb_c{k}")
            res = self._crash_at(X, y, path, [k])
            assert res.best_params_ == ref.best_params_, k
            assert res.best_score_ == ref.best_score_, k
            res_calls = {i: r[-1]["partial_fit_calls"]
                         for i, r in res.model_history_.items()}
            assert res_calls == ref_calls, k
            # bracket snapshots cleaned up after the successful resume
            assert not [f for f in os.listdir(path)
                        if f.endswith(".pkl")], k

    def test_double_crash(self, tmp_path, rng):
        """Crash, resume, crash again later, resume again."""
        X = rng.normal(size=(120, 3)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        ref = self._reference(X, y)
        res = self._crash_at(X, y, str(tmp_path / "hb_cc"), [1, 1])
        assert res.best_params_ == ref.best_params_
        assert res.best_score_ == ref.best_score_


class TestSearchCheckpointEdgeCases:
    """ISSUE 1 satellite: the SearchCheckpoint corners the crash matrix
    above doesn't isolate — the atomic-write window itself, foreign-
    snapshot preservation, and namedtuple state across a MESH change."""

    def test_crash_mid_atomic_write_keeps_previous_snapshot(self, tmp_path):
        """The checkpoint-write injection point fires BETWEEN the tmp
        write and the atomic rename: the previous snapshot must survive
        byte-identical and the tmp file must not leak."""
        from dask_ml_tpu.resilience import FaultInjected, fault_plan

        path = tmp_path / "s.pkl"
        ck = SearchCheckpoint(str(path), fingerprint="fp")
        ck.save({"m": 1}, {"i": [1]}, {"round": 1}, elapsed=1.0)
        first = path.read_bytes()

        with fault_plan() as plan:
            plan.inject("checkpoint-write", at_call=1)
            with pytest.raises(FaultInjected):
                ck.save({"m": 2}, {"i": [1, 2]}, {"round": 2}, elapsed=2.0)

        assert path.read_bytes() == first
        _, _, policy, elapsed = ck.load_if_matches()
        assert policy == {"round": 1} and elapsed == 1.0
        assert [p.name for p in tmp_path.iterdir()] == ["s.pkl"]

    def test_fingerprint_mismatch_keeps_foreign_snapshot_file(self, tmp_path):
        """A mismatched fingerprint starts fresh but must NOT consume or
        delete the foreign snapshot — it belongs to another search."""
        path = tmp_path / "s.pkl"
        SearchCheckpoint(str(path), fingerprint="theirs").save(
            {"m": 1}, {}, {"round": 3}
        )
        raw = path.read_bytes()

        ours = SearchCheckpoint(str(path), fingerprint="ours")
        assert ours.load_if_matches() is None
        assert path.read_bytes() == raw  # untouched on disk

    def test_namedtuple_state_resharded_across_mesh_change(self, tmp_path,
                                                           rng):
        """An estimator checkpoint holding a namedtuple solver-state attr
        with a ShardedRows leaf must round-trip onto a DIFFERENT mesh
        shape: the namedtuple rebuilds field-wise and the _ShardedMarker
        re-shards onto whatever mesh is active at load time."""
        import jax

        from dask_ml_tpu.core.mesh import device_mesh, use_mesh

        State = _SolverNTState

        n_dev = len(jax.devices())
        if n_dev < 2 or n_dev % 2:
            pytest.skip("needs an even device count >= 2 to halve the mesh")

        arr = rng.normal(size=(48, 4)).astype(np.float32)
        est = _SolverEst()
        est._solver_state = State(w=shard_rows(arr), step=5)
        est.coef_ = np.ones(4, np.float32)
        save_estimator(est, str(tmp_path / "solver"))

        half = device_mesh(n_dev // 2)
        with use_mesh(half):
            loaded = load_estimator(str(tmp_path / "solver"))
            st = loaded._solver_state
            assert isinstance(st, State) and st.step == 5
            # re-sharded over the SMALLER mesh, values intact
            assert len(st.w.data.sharding.device_set) == n_dev // 2
            np.testing.assert_allclose(unshard(st.w), arr, rtol=1e-6)
