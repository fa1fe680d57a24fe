"""``PCA(svd_solver="tsqr")`` from ``R`` alone (``linalg/tsqr.py :: tsqr_r``):
the mean, the Gram and the CholeskyQR2 repair as passes that keep (d, d)
state, the guard's verdict carried out, the Householder arm dispatched
only where the verdict asks; held to the benchmark's plain reference
(``benchmarks/references/pca_cov.py``, which imports nothing of
``dask_ml_tpu``) and to its limits on the 8-device mesh of ``conftest.py``,
and the benchmark's new files rehearsed small (arithmetic and verdicts,
never a time)."""

import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import diagnostics, obs
from dask_ml_tpu.core import device_mesh, shard_rows, unshard, use_mesh
from dask_ml_tpu.core.mesh import get_mesh
from dask_ml_tpu.decomposition import PCA, TruncatedSVD
from dask_ml_tpu.linalg import tsqr, tsqr_r

T = importlib.import_module("dask_ml_tpu.linalg.tsqr")

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import run as harness  # noqa: E402  (benchmarks/run.py: its loaders only)

CELL = "pca-tsqr.fit-1chip"
CONFIG = harness.load_json(BENCH, "configs", "pca-tsqr.json")
REFERENCE = harness.load_module("references", CONFIG["reference"])
GENERATOR = harness.load_module("generators", CONFIG["generator"])
COUNTS = harness.load_module("counts", CONFIG["counts"])
CPU_PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _table(seed, rows, chips=1, **params):
    """The cell's own kind of table, small, on ``chips`` devices."""
    params = dict(CONFIG["generator_params"], block_rows=rows // chips,
                  **params)
    return GENERATOR.make(harness.seed_key(jax, seed), rows, params,
                          harness.row_sharding(jax.devices()[:chips]))


def _answer(est):
    return harness.fetch_answer(np, est, CONFIG["fetch"])


def _fit_tree():
    """The last PCA fit's span tree (not ``obs.span_tree()``'s newest
    root: a thread an earlier test file left behind may open roots)."""
    roots = [r for r in obs.span_records()
             if r.name == "pca.fit" and r.parent_id is None]
    tree = obs.span_tree(roots[-1])
    return tree, {c["name"]: c for c in tree["children"]}


# ---- the fit against the plain reference -----------------------------------

@pytest.mark.parametrize("chips", [1, 2, 8])
def test_fit_agrees_with_the_plain_reference(chips):
    """``PCA.fit`` against the eigenvectors of the centred covariance, by
    the benchmark's own numbers and limits (their reason:
    ``limits_from`` in the configuration), on 1, 2 and 8 shards, the rows
    no multiple of the shards so that pad rows are there; and the same
    reference computed in bfloat16 fails one of them, so the limits would
    tell a fit in the precision below."""
    rows = 24_000 - 3
    whole = np.asarray(_table(11, 24_000)["X"])[:rows]
    data = {"X": jax.device_put(whole, harness.row_sharding(
        jax.devices()[:1])(2))}
    with use_mesh(device_mesh(chips)):
        sX = shard_rows(whole)
        assert sX.data.shape[0] > rows or chips == 1
        est = PCA(**CONFIG["estimator_args"]).fit(sX)
    assert est.n_passes_ == 3
    ref = REFERENCE.build(data, {})
    got = REFERENCE.compare(ref, data, _answer(est), {})
    assert set(got) == set(CONFIG["limits"])
    for name, limit in CONFIG["limits"].items():
        assert got[name] <= limit, (name, got[name])
    with use_mesh(device_mesh(1)):
        control = REFERENCE.control_estimator("bfloat16")().fit(
            shard_rows(whole))
    low = REFERENCE.compare(ref, data, _answer(control), {})
    assert any(low[name] > limit for name, limit in CONFIG["limits"].items())


def test_two_run_seeds_mirror_each_other():
    """The run seed flips feature columns: the fit's sums, Gram matrices
    and factorizations mirror with them, so the spectrum is the same to
    the bit and the components come out with the signs."""
    fits = []
    for seed in (5, 2**31 + 77):
        data = _table(seed, 8_000, features=16)
        est = PCA(svd_solver="tsqr").fit(shard_rows(data["X"]))
        fits.append((np.asarray(data["X"]), _answer(est)))
    (xa, a), (xb, b) = fits
    signs = np.sign(xa[0] * xb[0])
    assert set(np.unique(signs)) == {-1.0, 1.0}  # the seeds differ
    np.testing.assert_array_equal(xa * signs, xb)
    np.testing.assert_array_equal(a["explained_variance_"],
                                  b["explained_variance_"])
    np.testing.assert_array_equal(a["mean_"] * signs, b["mean_"])
    np.testing.assert_array_equal(np.abs(a["components_"]),
                                  np.abs(b["components_"]))


# ---- tsqr_r ------------------------------------------------------------------

@pytest.fixture
def tall(rng):
    return (rng.normal(size=(403, 10)) * np.linspace(3.0, 0.1, 10)
            + rng.uniform(-5, 5, size=10)).astype(np.float32)


@pytest.mark.parametrize("center", [None, "mean", "given"])
def test_r_is_tsqrs_up_to_row_signs(tall, center):
    """The R of ``tsqr_r`` is the R of ``tsqr`` on the centred, masked
    table, up to the signs of its rows (Cholesky's diagonal is positive,
    Householder's is not), with no Q made."""
    sX = shard_rows(tall)
    mu = {None: np.zeros(10, np.float32), "mean": tall.mean(axis=0),
          "given": np.arange(10, dtype=np.float32)}[center]
    r, mean, info = tsqr_r(
        sX, center=jnp.asarray(mu) if center == "given" else center)
    r = np.asarray(r, np.float64)
    assert info.tolist() == [3 if center == "mean" else 2, 1]
    if center is None:
        assert mean is None
    else:
        np.testing.assert_allclose(np.asarray(mean), mu, atol=1e-5)
    assert (np.diag(r) > 0).all()
    np.testing.assert_allclose(r, np.triu(r), atol=1e-6)
    z = tall.astype(np.float64) - mu
    np.testing.assert_allclose(r.T @ r, z.T @ z, rtol=1e-5, atol=1e-3)
    _, r_hh = tsqr(shard_rows((tall - mu).astype(np.float32)),
                   strategy="householder")
    np.testing.assert_allclose(np.abs(r), np.abs(np.asarray(r_hh)),
                               rtol=1e-3, atol=1e-4)


def test_householder_strategy_gives_the_same_r(tall, monkeypatch):
    monkeypatch.setenv("DASK_ML_TPU_TSQR", "householder")
    r_hh, mean, info = tsqr_r(shard_rows(tall), center="mean")
    assert info.tolist() == [2, 1]  # the mean's read and the QR's
    monkeypatch.setenv("DASK_ML_TPU_TSQR", "cholqr2")
    r, _, _ = tsqr_r(shard_rows(tall), center="mean")
    np.testing.assert_allclose(np.abs(np.asarray(r_hh)), np.abs(np.asarray(r)),
                               rtol=1e-3, atol=1e-4)
    with pytest.raises(ValueError, match="strategy"):
        tsqr_r(shard_rows(tall), strategy="bogus")
    with pytest.raises(ValueError, match="center"):
        tsqr_r(shard_rows(tall), center="median")
    with pytest.raises(ValueError, match="tall-skinny"):
        tsqr_r(shard_rows(np.ones((3, 40), np.float32)))


def test_ill_conditioned_table_takes_the_fallback_and_still_agrees(rng):
    """Duplicate columns: the Gram's Cholesky degenerates, the fetched
    verdict says so, the Householder arm is dispatched in its place (one
    more read of the table) and the answer is the PCA all the same."""
    A = rng.normal(size=(1201, 6)).astype(np.float32) * np.linspace(
        3.0, 0.5, 6).astype(np.float32) + 2.0
    X = np.concatenate([A, A[:, :3]], axis=1)
    r, mean, info = tsqr_r(shard_rows(X), center="mean")
    assert info.tolist() == [4, 0]
    z = X.astype(np.float64) - X.mean(axis=0, dtype=np.float64)
    r = np.asarray(r, np.float64)
    np.testing.assert_allclose(r.T @ r, z.T @ z, rtol=1e-4, atol=1e-2)

    est = PCA(svd_solver="tsqr").fit(shard_rows(X))
    assert est.n_passes_ == 4
    _, child = _fit_tree()
    assert child["pca.factor"]["attrs"] == {"passes": 4, "fallback": 1}
    want = np.linalg.eigvalsh(z.T @ z / (len(X) - 1))[::-1]
    np.testing.assert_allclose(np.asarray(est.explained_variance_)[:6],
                               want[:6], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(est.explained_variance_)[6:],
                               0.0, atol=1e-4)


@pytest.mark.parametrize("whiten", [False, True])
def test_fit_transform_is_fit_then_transform(tall, whiten):
    """``fit_transform`` is the fit and one product: the scores of
    ``transform`` on the same rows, the pad rows zero."""
    sX = shard_rows(tall)
    est = PCA(n_components=4, svd_solver="tsqr", whiten=whiten)
    scores = est.fit_transform(sX)
    np.testing.assert_allclose(unshard(scores), unshard(est.transform(sX)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(scores.data)[np.asarray(sX.mask) == 0], 0.0)
    plain = PCA(n_components=4, svd_solver="tsqr", whiten=whiten)
    np.testing.assert_allclose(np.asarray(plain.fit_transform(tall)),
                               unshard(scores), rtol=1e-4, atol=1e-4)


def test_truncated_svd_fits_from_r_alone(tall):
    """``TruncatedSVD`` takes R without a mean and its scores from one
    product: the scores' Gram is diag(s^2)."""
    est = TruncatedSVD(n_components=4)
    scores = unshard(est.fit_transform(shard_rows(tall))).astype(np.float64)
    s = np.linalg.svd(tall.astype(np.float64), compute_uv=False)[:4]
    np.testing.assert_allclose(np.asarray(est.singular_values_), s, rtol=1e-4)
    np.testing.assert_allclose(scores.T @ scores, np.diag(s ** 2),
                               rtol=1e-3, atol=1e-2)


def test_fit_holds_nothing_of_the_tables_size():
    """Compiled for one device at 1M x 64: the factorization's temporaries
    stay under a quarter of the table (a step's blocks and the 64 x 64
    state; neither Q nor a centred copy, each a whole table)."""
    rows, d = 1_000_000, 64
    with use_mesh(device_mesh(1)):
        mesh = get_mesh()
        spec = jax.sharding.PartitionSpec

        def S(shape, *axes):
            return jax.ShapeDtypeStruct(
                shape, jnp.float32,
                sharding=jax.sharding.NamedSharding(mesh, spec(*axes)))

        compiled = T._tsqr_r_impl.lower(
            S((rows, d), "data", None), S((rows,), "data"), S((d,)),
            mesh_holder=T._MeshHolder(mesh), find_mean=True,
            strategy="cholqr2").compile()
    assert compiled.memory_analysis().temp_size_in_bytes < rows * d * 4 / 4


# ---- spans, counters, programs -----------------------------------------------

def test_fit_leaves_its_spans_counts_and_no_second_compile(tall):
    reg = obs.registry()
    before = {n: reg.counter(n).value
              for n in ("pca.count", "pca.passes", "pca.fallbacks")}
    sX = shard_rows(tall)
    PCA(svd_solver="tsqr").fit(sX)
    misses = diagnostics.program_report()["totals"]["misses"]
    est = PCA(svd_solver="tsqr").fit(sX)
    assert diagnostics.program_report()["totals"]["misses"] == misses
    tree, child = _fit_tree()
    assert tree["attrs"] == {"solver": "tsqr", "rows": 403, "features": 10,
                             "chips": 8}
    assert list(child) == ["pca.factor", "pca.spectrum"]
    assert child["pca.factor"]["attrs"] == {"passes": 3, "fallback": 0}
    assert est.n_passes_ == 3
    after = {n: reg.counter(n).value for n in before}
    assert after["pca.count"] - before["pca.count"] == 2
    assert after["pca.passes"] - before["pca.passes"] == 6
    assert after["pca.fallbacks"] == before["pca.fallbacks"]
    programs = diagnostics.program_report()["programs"]
    assert {"tsqr.r", "pca.spectrum"} <= set(programs)


# ---- the benchmark's new files, small ----------------------------------------

def test_counts_by_hand():
    got = COUNTS.per_round(25_000_000, 64, {})
    table, gram = 6_400_000_000, 204_800_000_000
    assert got == {"bytes": table, "flops": gram // 3,
                   "factor_passes": [[table, 1_600_000_000], [table, gram],
                                     [table, 2 * gram]]}


def test_generator_makes_the_stated_spectrum():
    data = _table(3, 64_000)
    X = np.asarray(data["X"], np.float64)
    truth = data["truth"]
    assert data["y"] is None and X.shape == (64_000, 64)
    # sampling error of 64,000 rows: 1 / sqrt(n) of a standard deviation
    np.testing.assert_allclose(X.mean(axis=0), np.asarray(truth["mean"]),
                               atol=0.08)
    values = np.linalg.eigvalsh(np.cov(X.T))[::-1]
    np.testing.assert_allclose(values, np.asarray(truth["std"]) ** 2,
                               rtol=0.05)
    assert values[0] / values[-1] == pytest.approx(256, rel=0.1)
    v = np.asarray(truth["components"], np.float64)
    np.testing.assert_allclose(v @ v.T, np.eye(64), atol=1e-5)


def _small_cell():
    cell = harness.load_cell(CELL)
    cell["config_data"]["generator_params"]["block_rows"] = 25_000
    return cell


def test_cell_rehearsed_small(tmp_path):
    """The cell through ``run.run_cell``, traced, at 100,000 rows on the
    CPU: ``correct``, the result line's keys, and every metric a CPU
    trace can give (``factor.roof_pct`` needs device time)."""
    result = harness.run_cell(
        _small_cell(), 2**31 + 5, 0.2, True, devices=jax.devices()[:1],
        peaks=CPU_PEAKS, rows_per_chip=100_000, trace_dir=str(tmp_path))
    assert result["correct"] and result["failed"] == 0
    assert set(result["checks"]) == set(CONFIG["limits"])
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["factor.passes"] == 3 and metrics["solve.rounds"] == 3
    assert metrics["factor.fallbacks"] == 0
    assert metrics["window.compiles"] == 0
    assert metrics["factor.wall_ms"] > 0 and metrics["spectrum.wall_ms"] > 0


def test_roof_reader_by_hand():
    """``factor.roof_pct`` on a made-up trace: three reads of 6.4 GB at
    819 GB/s over 80 ms of the factor's module; nothing where no module
    ran or the counts carry no passes."""
    sX = shard_rows(np.random.RandomState(0).normal(size=(500, 8)).astype(
        np.float32))
    PCA(svd_solver="tsqr").fit(sX)  # the span tree the reader takes
    reader = harness.load_module("layer_metrics", "factor.roof_pct")
    ctx = {"trace": {"fits": [{"modules": {"jit__tsqr_r_fn": 0.080}}]},
           "cell": {"config_data": CONFIG},
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "least": COUNTS.per_round(25_000_000, 64, {})}
    assert reader.read(ctx) == pytest.approx(
        100 * 3 * 6.4e9 / 819e9 / 0.080)
    ctx["trace"] = {"fits": [{"modules": {}}]}
    assert reader.read(ctx) is None
    assert reader.read(dict(ctx, trace=None)) is None
    assert reader.read(dict(ctx, least={"bytes": 1, "flops": 1})) is None


@pytest.mark.parametrize("fault", ["control", "half_batch", "uncentred",
                                   "answer_altered"])
def test_control_and_planted_faults_read_not_correct(fault):
    """``control_pca.py`` small: the bfloat16 control and each fault that
    shows on the CPU fail one of the cell's limits (``repair_skipped`` is
    the TPU's: a CPU's float32 product loses nothing over its rows)."""
    import control_pca

    cell = _small_cell()
    real = harness.import_attr(CONFIG["estimator"])
    estimator = (REFERENCE.control_estimator("bfloat16") if fault == "control"
                 else control_pca.planted(real, fault))
    result = harness.run_cell(
        cell, 9, 0.0, False, devices=jax.devices()[:1], peaks=CPU_PEAKS,
        rows_per_chip=100_000, estimator=estimator)
    assert not result["correct"]
    assert any(value > limit for value, limit in result["checks"].values())
