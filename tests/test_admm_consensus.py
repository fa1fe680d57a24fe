"""ADMM's consensus over several shards (ISSUE 34): what the counted
runner carries out of the solve about it, the fit on four shards held to
the benchmark's plain reference (``benchmarks/references/
logistic_newton.py``, which imports nothing of ``dask_ml_tpu``) and to
the limits of the cell ``admm-higgs-250m.fit-4chip``, and that cell's new
files rehearsed small on the CPU mesh of ``conftest.py`` (arithmetic and
verdicts, never a time)."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import obs, solvers
from dask_ml_tpu.core import device_mesh, set_mesh, shard_rows, use_mesh
from dask_ml_tpu.linear_model import LogisticRegression
from dask_ml_tpu.solvers.algorithms import (
    SOLVE_COUNTS, SOLVE_RATIOS, unpack_counts)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
import control_consensus  # noqa: E402  (benchmarks/control_consensus.py)
import run as harness  # noqa: E402  (benchmarks/run.py)

CELL = "admm-higgs-250m.fit-4chip"
CONFIG = harness.load_json(BENCH, "configs", "admm-higgs-250m.json")
LIMITS = CONFIG["limits"]
REFERENCE = harness.load_module("references", CONFIG["reference"])
GENERATOR = harness.load_module("generators", CONFIG["generator"])
COUNTS = harness.load_module("counts", CONFIG["counts"])
CPU_PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
CHIPS = 4
#: rows of the CPU fits that are held to the limits read at 250M rows on
#: the chips: a fit stops about as near its optimum at any size, so a
#: sound one passes them from about a million rows on
ROWS = 1_000_000


def _table(seed, rows, chips=1):
    """The cell's own kind of table, small, on ``chips`` devices."""
    params = dict(CONFIG["generator_params"], block_rows=rows // chips)
    return GENERATOR.make(harness.seed_key(jax, seed), rows, params,
                          harness.row_sharding(jax.devices()[:chips]))


def _solve_span():
    """The last GLM fit's ``(root, glm.solve)`` attributes."""
    roots = [r for r in obs.span_records()
             if r.name == "glm.fit" and r.parent_id is None]
    tree = obs.span_tree(roots[-1])
    solve = next(c for c in tree["children"] if c["name"] == "glm.solve")
    return tree["attrs"], solve["attrs"]


@pytest.fixture()
def cell():
    """The cell as ``run.py`` loads it; ``run_cell`` sets the program's
    mesh, which is put back."""
    try:
        yield harness.load_cell(CELL)
    finally:
        set_mesh(None)


# ---- the fit against the plain reference -----------------------------------

@pytest.mark.parametrize("pad", [0, 3])
def test_fit_on_four_shards_agrees_with_the_plain_reference(pad):
    """The configuration's estimator on four shards against damped Newton
    on the stated objective, by the benchmark's own numbers under the
    cell's own limits (their reason: ``limits_from`` in the
    configuration); with ``pad`` the rows are no multiple of the shards,
    so pad rows are there and count nowhere."""
    rows = ROWS - pad
    whole = _table(11, ROWS)
    X, y = np.asarray(whole["X"])[:rows], np.asarray(whole["y"])[:rows]
    one = harness.row_sharding(jax.devices()[:1])
    data = {"X": jax.device_put(X, one(2)), "y": jax.device_put(y, one(1))}
    with use_mesh(device_mesh(CHIPS)):
        sX, sy = shard_rows(X), shard_rows(y)
        assert (sX.data.shape[0] > rows) == bool(pad)
        est = LogisticRegression(**CONFIG["estimator_args"]).fit(sX, sy)
    root, solve = _solve_span()
    assert root["n_shards"] == solve["shards"] == CHIPS
    answer = harness.fetch_answer(np, est, CONFIG["fetch"])
    ref = REFERENCE.build(data, CONFIG["estimator_args"])
    gaps = REFERENCE.compare(ref, data, answer, {})
    for name, limit in LIMITS.items():
        assert gaps[name] <= limit, (name, gaps, solve)


# ---- the parts add up ------------------------------------------------------

def _uneven(n=8000, d=6):
    """A table whose four runs of rows pose local problems of unlike
    conditioning, so that their local solves take unlike work."""
    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[n // 4: n // 2] *= 3.0
    X[3 * n // 4:] *= 0.3
    y = (X @ rng.normal(size=d) + rng.logistic(size=n) > 0)
    return X, y.astype(np.float32)


def test_the_same_table_on_one_two_and_four_shards_has_one_optimum():
    """The consensus is a way to the optimum of the WHOLE table's
    objective, however the rows are dealt: solved to the float32 floor of
    its residuals (tolerances far under it, 300 rounds) the answers on 1,
    2 and 4 shards agree to 1e-3 of the largest coefficient (measured
    1.7e-4 and 5.7e-5: what the residuals' float32 floor leaves)."""
    X, y = _uneven()
    kw = dict(lamduh=0.1, max_iter=300, inner_iter=50, intercept=True,
              abstol=1e-7, reltol=1e-6, inner_tol=1e-9, return_counts=True)
    found = {}
    for shards in (1, 2, 4):
        with use_mesh(device_mesh(shards)):
            beta, counts = solvers.admm(shard_rows(X), y, **kw)
        found[shards] = np.asarray(beta), unpack_counts(counts)[0]
    one = found[1][0]
    for shards in (2, 4):
        assert np.abs(found[shards][0] - one).max() <= 1e-3 * np.abs(
            one).max()
    assert found[1][1]["skew_passes"] == found[1][1]["skew_trials"] == 0


@pytest.mark.parametrize("line_search", ["backtrack", "probe_grid"])
def test_skew_is_the_slowest_shards_count_less_the_fastest(line_search):
    """One round on four shards: ``passes`` / ``trials`` are the slowest
    shard's, and less ``skew_*`` the fastest's, checked against each
    shard's rows solved alone (the same round on a mesh of one, where
    the skew reads 0)."""
    X, y = _uneven()
    n = len(y)
    kw = dict(lamduh=0.1, max_iter=1, inner_iter=30, intercept=True,
              line_search=line_search, return_counts=True)
    with use_mesh(device_mesh(4)):
        _, counts = solvers.admm(shard_rows(X), y, **kw)
    assert counts.dtype == jnp.int32
    assert counts.shape == (len(SOLVE_COUNTS) + len(SOLVE_RATIOS),)
    four, ratios = unpack_counts(counts)
    alone = []
    for i in range(4):
        rows = slice(i * n // 4, (i + 1) * n // 4)
        with use_mesh(device_mesh(1)):
            _, c = solvers.admm(shard_rows(X[rows]), y[rows], **kw)
        alone.append(unpack_counts(c)[0])
        assert alone[-1]["skew_passes"] == alone[-1]["skew_trials"] == 0
    for count, skew in (("passes", "skew_passes"), ("trials", "skew_trials")):
        each = [a[count] for a in alone]
        assert min(each) < max(each)  # the table is uneven enough
        assert four[count] == max(each)
        assert four[count] - four[skew] == min(each)
    assert four["rounds"] == 1 and four["rho_moves"] in (0, 1)
    # the round's first search has no history: under backtrack it starts
    # from the curvature's guess, and the most trials any shard took in
    # it ride out in the same vector (ISSUE 35); probe_grid guesses none
    guided = [a["guided_trials"] for a in alone]
    assert four["guided_trials"] == max(guided)
    assert (min(guided) >= 3 if line_search == "backtrack"
            else max(guided) == 0)
    # one round from a cold start has met nothing yet
    assert set(ratios) == set(SOLVE_RATIOS)
    assert all(np.isfinite(v) and v > 0 for v in ratios.values())


def test_where_the_consensus_stopped_rides_in_the_counts_vector():
    """The ratios are float32 bit patterns behind the integer counts:
    one vector, one transfer.  A solve that ended by its stopping rule
    reads both residuals under their tolerances, and ``rho_ratio`` is 1
    exactly where no round moved ``rho``; ``lbfgs`` fills the places
    every counted solver has and no more."""
    X, y = _uneven()
    with use_mesh(device_mesh(4)):
        sX = shard_rows(X)
        _, counts = solvers.admm(sX, y, lamduh=0.1, max_iter=200,
                                 intercept=True, return_counts=True)
        _, fixed = solvers.admm(sX, y, lamduh=0.1, max_iter=3,
                                adaptive_rho=False, rho=2.0,
                                return_counts=True)
        _, plain = solvers.lbfgs(sX, y, lamduh=0.1, return_counts=True)
    got, ratios = unpack_counts(counts)
    assert got["rounds"] < 200
    assert 0 <= ratios["primal_ratio"] < 1 and 0 <= ratios["dual_ratio"] < 1
    assert (ratios["rho_ratio"] == 1.0) == (got["rho_moves"] == 0)
    got, ratios = unpack_counts(fixed)
    assert got["rho_moves"] == 0 and ratios["rho_ratio"] == 1.0
    got, ratios = unpack_counts(plain)
    assert tuple(got) == SOLVE_COUNTS[:8] and ratios == {}


def test_glm_solve_span_and_registry_carry_the_consensus():
    from dask_ml_tpu import diagnostics

    X, y = _uneven()
    before = dict(diagnostics.run_report()["metrics"]["counters"])
    with use_mesh(device_mesh(4)):
        LogisticRegression(solver="admm", C=10.0).fit(
            shard_rows(X), shard_rows(y))
    root, solve = _solve_span()
    assert root["n_shards"] == solve["shards"] == 4 == root["chips"]
    assert set(SOLVE_COUNTS) | set(SOLVE_RATIOS) <= set(solve)
    assert solve["skew_passes"] > 0 and solve["skew_trials"] > 0
    after = diagnostics.run_report()["metrics"]["counters"]
    assert 3 * solve["rounds"] <= solve["guided_trials"] <= solve["trials"]
    for name in ("skew_passes", "skew_trials", "rho_moves", "guided_trials"):
        assert after["solve." + name] - before.get(
            "solve." + name, 0) == solve[name]
    assert not any(r in k for k in after for r in SOLVE_RATIOS)
    with use_mesh(device_mesh(4)):
        LogisticRegression(solver="lbfgs").fit(shard_rows(X), shard_rows(y))
    _, solve = _solve_span()
    assert solve["shards"] == 4 and "skew_passes" not in solve


def test_shard_rows_makes_its_mask_on_every_shard():
    """A device array's mask is born with the row sharding (eagerly it
    was made whole on the default device: 2.25 GB at 250M rows)."""
    x = jnp.arange(4 * 25 - 3, dtype=jnp.float32)
    with use_mesh(device_mesh(4)) as mesh:
        s = shard_rows(x)
        assert s.mask.sharding.is_equivalent_to(s.data.sharding, 1)
        assert len(s.mask.sharding.device_set) == 4
    np.testing.assert_array_equal(
        np.asarray(s.mask), (np.arange(100) < 97).astype(np.float32))
    assert s.n_samples == 97 and np.asarray(s.data)[97:].sum() == 0


# ---- the cell's new files, small -------------------------------------------

@pytest.mark.parametrize("chips", [1, 4])
def test_the_block_generator_makes_logistic_tables_table(chips):
    """``logistic_table_blocks`` (a loop over each chip's blocks) against
    ``logistic_table`` (one ``vmap`` over all, which a 62.5M-row share
    does not compile): the same ``X`` bit for bit and the same labels."""
    plain = harness.load_module("generators", "logistic_table")
    params = dict(CONFIG["generator_params"], block_rows=1000)
    sharding = harness.row_sharding(jax.devices()[:chips])
    key = harness.seed_key(jax, 2**31 + 5)
    want = plain.make(key, 16_000, params, sharding)
    got = GENERATOR.make(key, 16_000, params, sharding)
    assert got["X"].sharding.is_equivalent_to(want["X"].sharding, 2)
    np.testing.assert_array_equal(np.asarray(got["X"]), np.asarray(want["X"]))
    assert (np.asarray(got["y"]) != np.asarray(want["y"])).mean() < 1e-3
    np.testing.assert_array_equal(np.asarray(got["truth"]["w"]),
                                  np.asarray(want["truth"]["w"]))
    with pytest.raises(ValueError, match="whole blocks"):
        GENERATOR.make(key, 16_001 * chips, params, sharding)


def test_counts_and_the_roofline_reader_by_hand():
    plain = harness.load_module("counts", "logistic_pass")
    args = (62_500_000, 28, CONFIG["estimator_args"])
    assert COUNTS.per_round(*args) == plain.per_round(*args)
    assert COUNTS.per_trial(62_500_000) == {"bytes": 10**9}
    reader = harness.load_module("layer_metrics", "consensus.hbm_roof_pct")
    X, y = _uneven()
    with use_mesh(device_mesh(4)):
        LogisticRegression(solver="admm").fit(shard_rows(X), shard_rows(y))
    _, solve = _solve_span()
    ctx = {"trace": {"fits": [{"modules": {"jit__admm_run": 2.0}}]},
           "cell": {"config_data": CONFIG},
           "peaks": {"hbm_bytes_per_s": 1e6},
           "least": COUNTS.per_round(2000, 6, {})}
    streamed = solve["passes"] * 2000 * 6 * 4 + solve["trials"] * 16 * 2000
    assert reader.read(ctx) == pytest.approx(100 * streamed / 1e6 / 2.0)
    # a configuration whose count function states no trial: nothing
    ctx["cell"] = {"config_data": dict(CONFIG, counts="logistic_pass")}
    assert reader.read(ctx) is None
    ctx["trace"] = None
    assert reader.read(ctx) is None


def test_the_cell_runs_small_and_its_faults_read_over_the_limits(cell):
    """``run.run_cell`` on four CPU devices as ``control_consensus.py``
    drives it: the program passes, and the bfloat16 control, a dropped
    shard and a consensus that never met each fail a limit."""
    small = dict(cell, config_data=dict(
        CONFIG, generator_params=dict(
            CONFIG["generator_params"], block_rows=50_000)))
    out = control_consensus.readings(
        small, 2**31 + 77, devices=jax.devices()[:CHIPS], peaks=CPU_PEAKS,
        faults=("shard_dropped", "no_exchange"), rows_per_chip=ROWS // CHIPS)
    assert out["program"]["passes"], out
    for other in ("control.bfloat16", "fault.shard_dropped",
                  "fault.no_exchange"):
        assert not out[other]["passes"], (other, out)
        assert out[other]["newton_gap"] > 3 * LIMITS["newton_gap"], out
    # fewer rows answer farther from the whole table's optimum
    assert (out["fault.no_exchange"]["newton_gap"]
            > out["fault.shard_dropped"]["newton_gap"])


def test_the_faults_hand_the_fit_the_same_buffers():
    """``shard_dropped`` / ``no_exchange`` / ``half_batch`` make no copy
    of the table: the fit is handed the buffers of the chips it may see,
    on a mesh of those chips."""
    x = np.arange(40, dtype=np.float32).reshape(20, 2)
    with use_mesh(device_mesh(4)):
        rows = shard_rows(jnp.asarray(x))
        three = control_consensus.on_chips(rows, device_mesh(3))
        first = control_consensus.on_chips(rows, device_mesh(1))
    np.testing.assert_array_equal(np.asarray(three.data), x[:15])
    np.testing.assert_array_equal(np.asarray(first.data), x[:5])
    assert (three.n_samples, first.n_samples) == (15, 5)
    held = {s.device: s.data.unsafe_buffer_pointer()
            for s in rows.data.addressable_shards}
    for s in three.data.addressable_shards:
        assert s.data.unsafe_buffer_pointer() == held[s.device]
    seen = []

    class Est:
        def fit(self, X, y=None):
            from dask_ml_tpu.core.mesh import data_axes_size
            seen.append((data_axes_size(), X.n_samples))
            return self

    with use_mesh(device_mesh(4)):
        control_consensus.planted(Est, "shard_dropped")().fit(rows)
        control_consensus.planted(Est, "no_exchange")().fit(rows)
        control_consensus.planted(Est, "half_batch")().fit(rows)
    assert seen == [(3, 15), (1, 5), (2, 10)]
    with use_mesh(device_mesh(1)), pytest.raises(ValueError, match="several"):
        control_consensus.planted(Est, "no_exchange")().fit(
            shard_rows(jnp.asarray(x)))


def test_the_seam_reads_the_new_cell_on_several_shards():
    """``tests/test_benchmark_seam.py`` takes the new cell's program-side
    metrics as cases without an edit, and its small fit runs on the
    suite's mesh: more than one shard, so the consensus is there."""
    spec = importlib.util.spec_from_file_location(
        "seam", os.path.join(REPO, "tests", "test_benchmark_seam.py"))
    seam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(seam)
    for metric in ("consensus.skew_passes", "consensus.skew_trials"):
        assert (metric, CELL) in seam.PROGRAM_METRICS
    assert ("admm-higgs-250m", "jit__admm_run") in seam.NAMED_PROGRAMS
    seam._fit(seam.CONFIGS["admm-higgs-250m"])
    root, solve = _solve_span()
    assert root["n_shards"] == solve["shards"] == len(jax.devices()) > 1
