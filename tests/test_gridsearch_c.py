"""``GridSearchCV`` over ``C`` on sharded rows (ISSUE 39): an unshuffled
``KFold``'s folds cut on the device as slabs (``_split.py :: _fold_slabs``),
never as index arrays; the candidates of a fold as the lanes of one program
with their iteration counts kept; the search's spans and counts; held to the
benchmark's plain reference (``benchmarks/references/logistic_search.py``,
which imports nothing of ``dask_ml_tpu``) by the cell's own numbers on the
8-device mesh of ``conftest.py``, and the benchmark's new files rehearsed
small (arithmetic and verdicts, never a time)."""

import os
import sys
import warnings

import numpy as np
import pytest

import jax

from dask_ml_tpu import diagnostics, obs
from dask_ml_tpu.core import device_mesh, shard_rows, use_mesh
from dask_ml_tpu.linear_model import LogisticRegression
from dask_ml_tpu.model_selection import GridSearchCV, KFold, ShuffleSplit
from dask_ml_tpu.model_selection import _search, _split
from dask_ml_tpu.solvers import lambda_sweep
from dask_ml_tpu.solvers.algorithms import SOLVE_COUNTS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import run as harness  # noqa: E402  (benchmarks/run.py: its loaders only)

CELL = "gridsearch-c-higgs.fit-1chip"
CONFIG = harness.load_json(BENCH, "configs", "gridsearch-c-higgs.json")
REFERENCE = harness.load_module("references", CONFIG["reference"])
GENERATOR = harness.load_module("generators", CONFIG["generator"])
COUNTS = harness.load_module("counts", CONFIG["counts"])
MAKE = harness.import_attr(CONFIG["estimator"])
CPU_PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
GRID = CONFIG["estimator_args"]["param_grid"]


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """What ``auto`` means on a TPU, where the cell runs: the candidates
    pack into lanes; and the notice of unstratified folds is expected."""
    monkeypatch.setenv("DASK_ML_TPU_GRID_PACK", "packed")
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", "sharded input uses unshuffled")
        yield


def _logistic(rows, seed=0, features=28):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    y = X @ rng.normal(size=features) + 0.3 + rng.logistic(size=rows) > 0
    return X, y.astype(np.float32)


def _search_tree():
    """The last search's span tree (not ``obs.span_tree()``'s newest
    root: a thread an earlier test file left behind may open roots)."""
    roots = [r for r in obs.span_records()
             if r.name == "search.fit" and r.parent_id is None]
    tree = obs.span_tree(roots[-1])
    return tree, lambda name: [
        c for c in tree["children"] if c["name"] == name]


def _grid(cv=3, **kw):
    return GridSearchCV(LogisticRegression(solver="lbfgs"), GRID, cv=cv, **kw)


# ---- folds as slabs ----------------------------------------------------------

@pytest.mark.parametrize("cv", [2, 3, 5])
@pytest.mark.parametrize("chips", [1, 2, 8])
def test_slab_folds_are_take_by_the_sorted_indices_bit_for_bit(chips, cv):
    """Every fold of an unshuffled ``KFold``, cut by bounds, against the
    gather by the fold's sorted indices: X, y and both masks, pad rows and
    sharding too, on 1, 2 and 8 shards with rows no multiple of the shards
    or of the folds."""
    rows = 1003
    X, y = _logistic(rows, features=5)
    with use_mesh(device_mesh(chips)):
        sX, sy = shard_rows(X), shard_rows(y)
        splitter = KFold(cv)
        edges = splitter.bounds(rows)
        assert len(set(np.diff(edges))) == 2  # folds of two sizes
        for (tr, te), lo, hi in zip(splitter.split(X), edges[:-1], edges[1:]):
            Xtr, ytr, Xte, yte = _split._fold_slabs(sX, sy, lo, hi)
            for got, whole, idx in ((Xtr, sX, tr), (Xte, sX, te),
                                    (ytr, sy, tr), (yte, sy, te)):
                want = _split._take(whole, idx)
                assert got.n_samples == want.n_samples == len(idx)
                assert got.data.sharding.is_equivalent_to(
                    want.data.sharding, got.data.ndim)
                assert got.mask.sharding.is_equivalent_to(
                    want.mask.sharding, 1)
                np.testing.assert_array_equal(
                    np.asarray(got.data), np.asarray(want.data))
                np.testing.assert_array_equal(
                    np.asarray(got.mask), np.asarray(want.mask))


def test_a_table_without_labels_is_cut_alone():
    sX = shard_rows(_logistic(101, features=3)[0])
    Xtr, ytr, Xte, yte = _split._fold_slabs(sX, None, 10, 40)
    assert (Xtr.n_samples, Xte.n_samples, ytr, yte) == (71, 30, None, None)


@pytest.mark.parametrize("cv", [3, "KFold(3)", None])
def test_the_slab_path_makes_no_index(cv, monkeypatch):
    """``cv=<int>``, the default and this package's own unshuffled
    ``KFold`` on sharded rows: ``KFold.split`` is never called, nothing is
    taken by index, and the folds' spans count no byte made on the host."""

    def never(*a, **k):
        raise AssertionError("an index path ran under a slab search")

    monkeypatch.setattr(KFold, "split", never)
    monkeypatch.setattr(_search, "_rows", never)
    monkeypatch.setattr(_split, "_take", never)
    X, y = _logistic(1501)
    search = _grid(KFold(3) if cv == "KFold(3)" else cv).fit(
        shard_rows(X), shard_rows(y))
    folds = 5 if cv is None else 3
    assert search.n_splits_ == folds
    tree, spans = _search_tree()
    assert spans("search.split")[0]["attrs"] == {
        "splitter": "KFold", "slabs": 1, "folds": folds}
    made = spans("search.fold")
    assert [s["attrs"]["fold"] for s in made] == list(range(folds))
    assert all(s["attrs"]["host_index_bytes"] == 0 for s in made)
    assert sum(s["attrs"]["rows_test"] for s in made) == 1501
    assert all(s["attrs"]["rows_train"] + s["attrs"]["rows_test"] == 1501
               for s in made)


def _explicit(n):
    idx = np.arange(n)
    return [(idx[idx % 3 != k], idx[idx % 3 == k]) for k in range(3)]


@pytest.mark.parametrize("splitter", [
    "shuffled", "ShuffleSplit", "sklearn", "iterable", "host labels"])
def test_every_other_splitter_keeps_the_index_path(splitter, monkeypatch):
    """Shuffled folds, ``ShuffleSplit``, scikit-learn's ``KFold``, an
    explicit list of index pairs, and the stratified default that host
    labels get: folds by index, as before, their bytes counted."""
    import sklearn.model_selection as sk

    def never(*a, **k):
        raise AssertionError("slabs cut for a splitter with no slabs")

    monkeypatch.setattr(_search, "_fold_slabs", never)
    rows = 1502
    X, y = _logistic(rows)
    cv = {"shuffled": KFold(3, shuffle=True, random_state=0),
          "ShuffleSplit": ShuffleSplit(3, test_size=0.3, random_state=0),
          "sklearn": sk.KFold(3), "iterable": _IterableCV(_explicit(rows)),
          "host labels": 3}[splitter]
    search = _grid(cv).fit(
        shard_rows(X), y if splitter == "host labels" else shard_rows(y))
    assert np.isfinite(search.cv_results_["mean_test_score"]).all()
    tree, spans = _search_tree()
    assert spans("search.split")[0]["attrs"]["slabs"] == 0
    made = spans("search.fold")
    assert len(made) == 3
    shards = len(jax.devices())
    for s in made:
        sides = (s["attrs"]["rows_train"], s["attrs"]["rows_test"])
        sharded = 1 if splitter == "host labels" else 2  # X, and y
        # the splitter's own int64 pair ONCE and, for every sharded array
        # and side, an int32 index and a float32 mask padded to the shards
        assert s["attrs"]["host_index_bytes"] == 8 * sum(sides) + sum(
            sharded * 8 * (k + (-k) % shards) for k in sides)


class _IterableCV:
    """An explicit list of (train, test) pairs, in the splitter's shape."""

    def __init__(self, pairs):
        self.pairs = pairs

    def split(self, X, y=None, groups=None):
        return iter(self.pairs)

    def get_n_splits(self, X=None, y=None, groups=None):
        return len(self.pairs)


def test_the_unstratified_notice_comes_once_a_fit():
    X, y = _logistic(600)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _grid().fit(shard_rows(X), shard_rows(y))
        said = [w for w in caught if "unshuffled KFold" in str(w.message)]
        assert len(said) == 1 and said[0].filename == __file__
        caught.clear()
        _grid(KFold(3)).fit(shard_rows(X), shard_rows(y))  # asked for
        assert not [w for w in caught if "unshuffled" in str(w.message)]


# ---- the packed search -------------------------------------------------------

def test_packed_search_is_the_per_candidate_search(monkeypatch):
    """The lanes against one fit a candidate and fold
    (``DASK_ML_TPU_GRID_PACK=sequential``): the same folds, so scores a
    few rows apart at most (the lanes search the line on the black box,
    a single fit on its cached predictor), the same choice, the same
    refit."""
    rows = 6001
    X, y = _logistic(rows, seed=3)
    sX, sy = shard_rows(X), shard_rows(y)
    packed = _grid().fit(sX, sy)
    assert _search_tree()[0]["attrs"]["packed"] == 1
    monkeypatch.setenv("DASK_ML_TPU_GRID_PACK", "sequential")
    single = _grid().fit(sX, sy)
    tree, spans = _search_tree()
    assert tree["attrs"]["packed"] == 0 and not spans("search.sweep")
    assert len(spans("search.fold")) == 3  # made once, shared by the eight
    # the lanes' coefficients are the packed path's to leave, and an
    # earlier fit's do not outlive a fit that packed nothing
    assert packed.coefs_paths_.shape == (3, 8, 29)
    assert not hasattr(single, "coefs_paths_")
    for i in range(3):
        np.testing.assert_allclose(
            packed.cv_results_[f"split{i}_test_score"],
            single.cv_results_[f"split{i}_test_score"], atol=3.0 / 2000)
    np.testing.assert_allclose(packed.cv_results_["mean_test_score"],
                               single.cv_results_["mean_test_score"],
                               atol=1e-3)
    assert packed.best_params_ == single.best_params_
    np.testing.assert_array_equal(
        np.asarray(packed.best_estimator_.coef_),
        np.asarray(single.best_estimator_.coef_))
    assert not hasattr(packed.fit(sX, sy), "coefs_paths_")


def test_search_leaves_its_spans_counts_and_no_second_compile():
    """``search.fit`` > ``search.split``, then a fold's ``search.fold``,
    ``search.sweep``, ``search.score`` three times, then ``search.refit``
    over the winner's own ``glm.fit``; the lanes' counts as stated; the
    registry's counts; and a second search compiles nothing."""
    X, y = _logistic(3001)
    sX, sy = shard_rows(X), shard_rows(y)
    _grid().fit(sX, sy)

    def book():
        counters = diagnostics.run_report()["metrics"]["counters"]
        return (np.array([counters.get("search." + k, 0)
                          for k in ("fits", "folds", "lanes")]),
                diagnostics.program_report()["totals"]["misses"])

    counts, misses = book()
    with jax.log_compiles():
        import logging

        seen = []
        handler = logging.Handler()
        handler.emit = lambda record: seen.append(record.getMessage())
        log = logging.getLogger("jax._src.dispatch")
        log.addHandler(handler)
        try:
            search = _grid().fit(sX, sy)
        finally:
            log.removeHandler(handler)
    assert not [m for m in seen if "Finished XLA compilation" in m]
    after, misses_after = book()
    assert misses_after == misses
    np.testing.assert_array_equal(after - counts, [1, 3, 24])

    tree, spans = _search_tree()
    assert tree["attrs"] == {
        "search": "GridSearchCV", "estimator": "LogisticRegression",
        "candidates": 8, "folds": 3, "packed": 1}
    assert [c["name"] for c in tree["children"]] == (
        ["search.split"] + ["search.fold", "search.sweep", "search.score"] * 3
        + ["search.refit"])
    for fold, sweep in enumerate(spans("search.sweep")):
        a = sweep["attrs"]
        assert a["fold"] == fold and a["lanes"] == 8
        assert 1 <= a["iters_min"] <= a["iters_max"] <= 100
        # the lanes' own counts, from the solver the span's came from
        Xtr, ytr, _, _ = _split._fold_slabs(
            sX, sy, *KFold(3).bounds(3001)[fold:fold + 2])
        betas, _classes, counts = LogisticRegression(
            solver="lbfgs")._sweep_fit_binary(Xtr, ytr, GRID["C"])
        assert isinstance(betas, np.ndarray) and betas.shape == (8, 29)
        np.testing.assert_array_equal(search.coefs_paths_[fold], betas)
        lane = dict(zip(SOLVE_COUNTS, counts.T.astype(int)))
        iters, passes = lane["rounds"], lane["passes"]
        assert (a["iters_max"], a["iters_min"]) == (iters.max(), iters.min())
        assert a["lane_idle_iters"] == int((iters.max() - iters).sum())
        # a black box's trials are passes (none counted apart), all but
        # one at the start and one an iteration
        assert not lane["trials"].any() and (passes > 2 * iters).all()
        assert a["passes_max"] == passes.max()
        assert a["trials_max"] == (passes - iters - 1).max() > 0
    refit = spans("search.refit")[0]
    assert refit["attrs"]["candidate"] == search.best_index_
    assert [c["name"] for c in refit["children"]] == ["glm.fit"]
    assert refit["children"][0]["attrs"]["rows"] == 3001


@pytest.mark.parametrize("solver", ["lbfgs", "admm", "newton"])
def test_lanes_return_their_counts_on_request(solver):
    """``lambda_sweep(return_counts=True)``: each lane's whole count in
    the iterations' place, the iterations first; the coefficients and
    the iterations are what the plain call returns."""
    X, y = _logistic(1203)
    sX, sy = shard_rows(X), shard_rows(y)
    betas, iters = lambda_sweep(solver, sX, sy, [1.0, 0.01])
    again, counts = lambda_sweep(solver, sX, sy, [1.0, 0.01],
                                 return_counts=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(betas))
    counts = np.asarray(counts)
    assert counts.shape[0] == 2 and counts.ndim == 2
    np.testing.assert_array_equal(counts[:, 0], np.asarray(iters))
    assert ("passes" in dict(zip(SOLVE_COUNTS, counts.T))) == (
        solver != "newton")


def test_a_finished_search_holds_nothing_of_the_tables_size():
    """Once the caller lets go of the table and of the search, no array
    of a fold's or the table's length is alive, with the collector off:
    the search's closures make no cycle that would keep the table on the
    device until a collection (a second table then finds no room)."""
    import gc

    def big():
        return [a.shape for a in jax.live_arrays()
                if a.shape and a.shape[0] >= 2000]

    before = big()
    gc.collect()
    gc.disable()
    try:
        X, y = _logistic(6007)
        coef = np.asarray(_grid().fit(
            shard_rows(X), shard_rows(y)).best_estimator_.coef_)
        assert coef.shape == (28,) and big() == before
    finally:
        gc.enable()


# ---- the search against the plain reference ----------------------------------

def _limits(rows_test):
    """The cell's numbers under limits for a table of 24,000 rows on the
    CPU.  The cell's own were read at 31.25M rows on the chip, and its
    readings move with the size: a score moves by 1e-7 a row there and
    by 1 / rows_test here, so ``score_gap`` gets three rows beside the
    cell's limit (read here: the program two rows from the reference,
    the bfloat16 control five) and ``choice_gap`` stands a thousandth of
    a row over nought; and L-BFGS's relative-decrease stop leaves a fit
    farther from the optimum on a small table (read here, on 1 and 8
    shards: the refit's ``newton_gap`` 5.81e-4 and ``grad_gap`` 2.45e-4,
    the bfloat16 control 1.34e-3 and 5.98e-4; the lanes' farthest
    ``lane_newton_gap`` 1.53e-3, the control 4.54e-3: the CPU's products
    are float32, where the chip's lanes read 6.0e-3, OVER the control's
    4.1e-3), so those three stand at the geometric middle of this size's
    two readings."""
    cell = CONFIG["limits"]
    return {"score_gap": cell["score_gap"] + 3.0 / rows_test,
            "choice_gap": 1e-3 / rows_test,
            "lane_newton_gap": 2.6e-3,
            "newton_gap": 8.8e-4, "grad_gap": 3.8e-4}


@pytest.mark.parametrize("chips", [1, 8])
def test_search_agrees_with_the_plain_reference(chips):
    """The search against Newton on each fold's train rows and a count on
    its held-out rows, by the benchmark's own numbers and limits, on 1
    and 8 shards with pad rows; and the same reference computed in
    bfloat16 fails one of them, so the limits would tell a search in the
    precision below."""
    rows = 24_000 - 3
    whole = _table(11, 24_000)
    X, y = np.asarray(whole["X"])[:rows], np.asarray(whole["y"])[:rows]
    one = harness.row_sharding(jax.devices()[:1])
    data = {"X": jax.device_put(X, one(2)), "y": jax.device_put(y, one(1))}
    with use_mesh(device_mesh(chips)):
        sX, sy = shard_rows(X), shard_rows(y)
        assert sX.data.shape[0] > rows or chips == 1
        search = MAKE(**CONFIG["estimator_args"]).fit(sX, sy)
    ref = REFERENCE.build(data, CONFIG["estimator_args"])
    got = REFERENCE.compare(ref, data, _answer(search), {})
    limits = _limits(rows // 3)
    # ``regret`` is computed and under no limit: on this grid no choice
    # moves it (PERF.md section 7)
    assert set(got) == set(limits) | {"regret"}
    assert set(limits) == set(CONFIG["limits"])
    for name, limit in limits.items():
        assert got[name] <= limit, (name, got[name])
    with use_mesh(device_mesh(1)):
        control = REFERENCE.control_estimator("bfloat16")(
            **CONFIG["estimator_args"]).fit(shard_rows(X), shard_rows(y))
    low = REFERENCE.compare(ref, data, _answer(control), {})
    assert any(low[name] > limit for name, limit in limits.items()), low
    # where the products are float32 (here), the lanes' own number tells
    # the precision below, not only the refit's
    assert low["lane_newton_gap"] > limits["lane_newton_gap"]


def test_reference_states_its_own_folds():
    """Thirds by ``linspace`` cut to integers: the rule the program's
    ``KFold`` states too, written twice."""
    for n, k in ((31_250_000, 3), (1003, 5), (10, 3)):
        assert REFERENCE.fold_bounds(n, k) == list(zip(
            KFold(k).bounds(n)[:-1], KFold(k).bounds(n)[1:]))
    assert REFERENCE.fold_bounds(31_250_000, 3) == [
        (0, 10_416_666), (10_416_666, 20_833_333), (20_833_333, 31_250_000)]


def _table(seed, rows, chips=1):
    """The cell's own kind of table, small, on ``chips`` devices."""
    params = dict(CONFIG["generator_params"], block_rows=rows // chips)
    return GENERATOR.make(harness.seed_key(jax, seed), rows, params,
                          harness.row_sharding(jax.devices()[:chips]))


def _answer(est):
    return harness.fetch_answer(np, est, CONFIG["fetch"])


# ---- the benchmark's new files, rehearsed small ------------------------------

def _small_cell():
    cell = harness.load_cell(CELL)
    cell["config_data"]["generator_params"]["block_rows"] = 12_000
    cell["config_data"]["limits"] = _limits(8_000)
    return cell


def test_cell_rehearsed_small(tmp_path):
    """The cell through ``run.run_cell``, traced, at 24,000 rows on the
    CPU: ``correct``, the result line's keys, and every metric a CPU
    trace can give (``sweep.hbm_roof_pct`` needs device time)."""
    result = harness.run_cell(
        _small_cell(), 2**31 + 5, 0.2, True, devices=jax.devices()[:1],
        peaks=CPU_PEAKS, rows_per_chip=24_000, trace_dir=str(tmp_path))
    assert result["correct"] and result["failed"] == 0
    assert set(result["checks"]) == set(CONFIG["limits"])
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["solve.rounds"] == 3 and metrics["window.compiles"] == 0
    assert metrics["search.fold_host_mb"] == 0
    assert metrics["search.lane_idle_iters"] > 0
    assert metrics["sweep.passes"] > metrics["sweep.trials"] > 0
    for name in ("search.fold_ms", "search.sweep_ms", "search.score_ms",
                 "search.refit_ms"):
        assert metrics[name] > 0, name


def test_counts_and_roof_reader_by_hand():
    """A fold's least work, and ``sweep.hbm_roof_pct`` on a made-up
    trace: 20 + 22 + 21 iterations x one read of 2.33 GB at 819 GB/s over
    0.9 s of the lanes' module; nothing where no module ran or the span
    carries no count."""
    least = COUNTS.per_round(31_250_000, 28, CONFIG["estimator_args"])
    assert least["bytes"] == 31_250_000 * 28 * 4
    assert least["train_bytes"] == 20_833_334 * 28 * 4
    assert least["test_bytes"] == 10_416_666 * 28 * 4
    assert least["flops"] == (4 * 20_833_334 + 16 * 10_416_666) * 28
    X, y = _logistic(900)
    _grid().fit(shard_rows(X), shard_rows(y))  # the tree the reader takes
    iters = [s["attrs"]["iters_max"] for s in _search_tree()[1]("search.sweep")]
    reader = harness.load_module("layer_metrics", "sweep.hbm_roof_pct")
    ctx = {"trace": {"fits": [{"modules": {"jit__sweep_lanes": 0.9,
                                           "jit__lbfgs_run": 0.1}}]},
           "cell": {"config_data": CONFIG}, "least": least,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert reader.read(ctx) == pytest.approx(
        100 * sum(iters) * least["train_bytes"] / 819e9 / 0.9)
    assert reader.read(dict(ctx, trace={"fits": [{"modules": {}}]})) is None
    assert reader.read(dict(ctx, trace=None)) is None
    assert reader.read(dict(ctx, least={"bytes": 1, "flops": 1})) is None


#: the numbers that tell each fault here, and no other does
TOLD_BY = {
    "control": {"score_gap", "lane_newton_gap", "newton_gap", "grad_gap"},
    "scored_on_train": {"score_gap"}, "fold_dropped": {"score_gap"},
    "half_batch": {"score_gap", "lane_newton_gap", "newton_gap",
                   "grad_gap"},
    "refit_skipped": {"newton_gap", "grad_gap"},
    "wrong_choice": {"choice_gap"},
}


@pytest.mark.parametrize("fault", list(TOLD_BY))
def test_control_and_planted_faults_read_not_correct(fault):
    """The bfloat16 control and each guarantee of a search turned off
    (``benchmarks/control_search.py``), under the timed path at 24,000
    rows: every one fails at least one limit, and a wrong choice of ``C``
    the one number that reads the choice."""
    import control_search

    estimator = (REFERENCE.control_estimator("bfloat16") if fault == "control"
                 else control_search.planted(MAKE, fault))
    result = harness.run_cell(
        _small_cell(), 9, 0.0, False, devices=jax.devices()[:1],
        peaks=CPU_PEAKS, rows_per_chip=24_000, estimator=estimator)
    assert not result["correct"]
    assert {name for name, (value, limit) in result["checks"].items()
            if value > limit} == TOLD_BY[fault]
