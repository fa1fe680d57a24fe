"""Multi-host plane tests (VERDICT round-1 item 4; SURVEY.md §2.3).

The in-process suite runs on one process, so the cross-process path is
exercised the way the reference exercises multi-node behavior — a real
protocol stack on localhost (``gen_cluster`` analogue): subprocesses form a
``jax.distributed`` group with Gloo CPU collectives and run the flagship
SPMD programs over the global mesh.
"""

import os
import sys

import jax
import pytest

from conftest import retry_flaky
from dask_ml_tpu.core._multihost_worker import spawn_group

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestMultihost:
    def test_two_process_admm_and_lloyd(self):
        outs = []
        for rc, out in spawn_group(2, 4, timeout_s=720):
            assert rc == 0, out
            assert "multihost OK" in out
            # flagship 5: ADMM + Lloyd over the hierarchical
            # ('dcn','data','model') mesh with dcn spanning the two
            # processes, parity-asserted against the flat-mesh fits
            # inside the worker
            assert "dcn_mesh OK" in out
            outs.append(out)
        # cross-host packed search: the worker runs a
        # 4-model IncrementalSearchCV with the cohort's MODEL_AXIS spanning
        # both processes; every dispatch must step the whole cohort and
        # both processes must agree on every score
        import ast
        import re

        parsed = []
        for out in outs:
            m = re.search(r"search_scores=(\[[^\]]*\])", out)
            assert m, out
            parsed.append(ast.literal_eval(m.group(1)))
            s = re.search(r"dispatch_stats=(\{[^}]*\})", out)
            stats = ast.literal_eval(s.group(1))
            assert stats["models_stepped"] == 4 * stats["dispatches"], stats
        assert parsed[0] == parsed[1]  # identical across processes

        # sequential-bracket Hyperband (flagship 4): both processes must
        # report the identical best score and model count — the whole
        # point of the lockstep form is cross-controller agreement
        hbs = []
        for out in outs:
            m = re.search(r"hyperband_best=([0-9.]+) n_models=(\d+)", out)
            assert m, out
            hbs.append((m.group(1), m.group(2)))
        assert hbs[0] == hbs[1], hbs

        # identical to single-host: the same global dataset on one
        # process's 8-device mesh must produce the same scores
        import numpy as np

        from dask_ml_tpu.core import shard_rows
        from dask_ml_tpu.core.mesh import device_mesh, use_mesh
        from dask_ml_tpu.linear_model import SGDClassifier
        from dask_ml_tpu.model_selection import IncrementalSearchCV

        n_per, d = 400, 6
        rng = np.random.RandomState(0)
        w_true = rng.normal(size=d).astype(np.float32)
        Xg = np.vstack([
            np.random.RandomState(100 + pid).normal(
                size=(n_per, d)).astype(np.float32)
            for pid in range(2)
        ])
        yg = (Xg @ w_true > 0).astype(np.float32)
        from conftest import require_devices_divisible

        mesh2 = device_mesh(require_devices_divisible(2), model_axis=2)
        with use_mesh(mesh2):
            search = IncrementalSearchCV(
                SGDClassifier(random_state=0, tol=None),
                {"alpha": [1e-5, 1e-4, 1e-3, 1e-2]},
                n_initial_parameters="grid", max_iter=3, patience=False,
                random_state=0,
            ).fit(shard_rows(Xg, mesh2), shard_rows(yg, mesh2),
                  classes=[0.0, 1.0])
        single = [round(s, 6) for s in search.cv_results_["test_score"]]
        np.testing.assert_allclose(single, parsed[0], atol=1e-4)

    @retry_flaky(
        attempts=2,
        match=(r"heartbeat|coordination.?service|barrier.*timed?.?out|"
               r"deadline.?exceeded|unavailable"),
    )
    def test_three_process_group(self):
        """Odd process count (3 × 2 devices): the mesh math, the
        hierarchical dcn axis (size 3), and the cross-controller
        agreement must all be nproc-generic, not 2-hardcoded.  All
        three processes must report identical search scores and
        Hyperband results.

        Auto-retried on heartbeat/coordination noise only: 3 jax
        processes on the 2-core box intermittently starve the
        coordination service (ROADMAP env note) — that flake class
        passes in isolation and must not eat a tier-1 lane, while any
        real score/agreement assertion still fails on the first run.
        """
        import re

        outs = []
        for rc, out in spawn_group(3, 2, timeout_s=900):
            assert rc == 0, out
            assert "multihost OK" in out
            assert "dcn_mesh OK" in out
            outs.append(out)
        scores = [re.search(r"search_scores=(\[[^\]]*\])", o).group(1)
                  for o in outs]
        assert scores[0] == scores[1] == scores[2]
        hbs = [re.search(r"hyperband_best=([0-9.]+) n_models=(\d+)",
                         o).groups() for o in outs]
        assert hbs[0] == hbs[1] == hbs[2]

    def test_graft_entry_dryrun_multihost(self):
        # the driver-facing wrapper end-to-end
        sys.path.insert(0, REPO)
        try:
            import __graft_entry__ as g

            g.dryrun_multihost(2, local_devices=2)
        finally:
            sys.path.remove(REPO)


class TestRetryFlaky:
    """The auto-retry harness itself: retries ONLY the matched flake
    class, surfaces real failures immediately."""

    def test_matched_flake_is_retried(self):
        calls = []

        @retry_flaky(attempts=2, match="heartbeat")
        def flaky():
            calls.append(1)
            if len(calls) == 1:
                raise AssertionError("coordination heartbeat timed out")
            return "ok"

        with pytest.warns(UserWarning, match="retrying"):
            assert flaky() == "ok"
        assert len(calls) == 2

    def test_unmatched_failure_is_not_retried(self):
        calls = []

        @retry_flaky(attempts=3, match="heartbeat")
        def broken():
            calls.append(1)
            raise AssertionError("scores diverged across processes")

        with pytest.raises(AssertionError, match="diverged"):
            broken()
        assert len(calls) == 1

    def test_exhausted_retries_raise_the_flake(self):
        @retry_flaky(attempts=2, match="heartbeat")
        def always():
            raise RuntimeError("heartbeat lost")

        with pytest.warns(UserWarning, match="retrying"):
            with pytest.raises(RuntimeError, match="heartbeat"):
                always()


class TestGlobalMeshSingleProcess:
    """Mesh/axis logic that doesn't need a real process group."""

    def test_global_mesh_flat_axes(self, mesh):
        from dask_ml_tpu.core import distributed as dist

        m = dist.global_mesh()
        assert m.axis_names == ("data", "model")
        assert len(m.devices.flat) == len(jax.devices())

    def test_hierarchical_single_process(self, mesh):
        from dask_ml_tpu.core import distributed as dist

        m = dist.global_mesh(hierarchical=True)
        assert m.axis_names == ("dcn", "data", "model")
        assert m.shape["dcn"] == 1  # one process

    def test_shard_rows_global_single_process(self, mesh, rng):
        import numpy as np

        from dask_ml_tpu.core import distributed as dist
        from dask_ml_tpu.core import unshard

        X = rng.normal(size=(37, 3)).astype(np.float32)
        s = dist.shard_rows_global(X, dist.global_mesh())
        assert s.n_samples == 37
        np.testing.assert_allclose(unshard(s), X)

    def test_mesh_process_mismatch_clear_error(self, mesh):
        import numpy as np
        import pytest

        from dask_ml_tpu.core import distributed as dist

        from conftest import require_devices_divisible

        require_devices_divisible(8)
        m = dist.global_mesh(model_axis=8)  # data axis size 1, 1 process ok
        # fake a larger process count via monkeypatching is brittle; instead
        # check the validation logic directly
        with pytest.raises(ValueError, match="evenly"):
            # simulate: 1 data shard cannot split over 2 processes
            import jax

            orig = jax.process_count
            jax.process_count = lambda: 2
            try:
                dist.shard_rows_global(np.zeros((4, 2), np.float32), m)
            finally:
                jax.process_count = orig


class TestHierarchicalMeshCompat:
    """Every shard_map program now runs NATIVELY on the ('dcn','data')
    axis tuple (``core.mesh.data_axes``): TSQR's R all_gather and the
    pairwise ppermute ring span the slice boundary (flattened ring
    semantics over the tuple), ADMM's psums likewise (covered by the
    worker flagship).  This pin proves correctness of those collectives
    on a mesh whose rows are genuinely split over BOTH axes."""

    def test_programs_correct_on_dcn_mesh(self, rng):
        import numpy as np

        from conftest import require_devices_divisible

        require_devices_divisible(8)
        from dask_ml_tpu.core import use_mesh
        from dask_ml_tpu.core import distributed as dist
        from dask_ml_tpu.core.mesh import Mesh

        devs = np.array(jax.devices()[:8]).reshape(2, 4, 1)
        hmesh = Mesh(devs, ("dcn", "data", "model"))
        X = rng.normal(size=(160, 6)).astype(np.float32)
        with use_mesh(hmesh):
            s = dist.shard_rows_global(X, hmesh)
            # rows genuinely split over BOTH axes
            assert "dcn" in str(s.data.sharding.spec)

            from dask_ml_tpu.linalg.tsqr import tsqr

            q, r = tsqr(s)
            qh = np.asarray(q)[:160].astype(np.float64)
            rr = np.asarray(r).astype(np.float64)
            assert np.abs(qh @ rr - X).max() < 1e-5
            assert np.abs(qh.T @ qh - np.eye(6)).max() < 1e-5

            from sklearn.metrics.pairwise import (
                euclidean_distances as sk_euc,
            )

            from dask_ml_tpu.metrics import euclidean_distances

            Y = dist.shard_rows_global(X[:80], hmesh)
            d_ring = np.asarray(euclidean_distances(s, Y))
            ref = sk_euc(X.astype(np.float64), X[:80].astype(np.float64))
            assert np.abs(d_ring - ref).max() < 1e-5
