"""k-means|| in memory bounded by the table (``cluster/k_means.py``): the
carried least distance and nearest slot, the fixed-capacity candidate
buffer, the sort-free compaction and the per-shard program, held to brute
force and to the benchmark's plain reference
(``benchmarks/references/kmeans_lloyd.py``, which imports nothing of
``dask_ml_tpu``) on the 8-device mesh of ``conftest.py``."""

import functools
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import diagnostics, obs
from dask_ml_tpu.cluster import KMeans
from dask_ml_tpu.cluster import k_means as km
from dask_ml_tpu.core import device_mesh, shard_rows, use_mesh
from dask_ml_tpu.core.mesh import MeshHolder, get_mesh
from dask_ml_tpu.utils import reweight_rows

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import run as harness  # noqa: E402  (benchmarks/run.py: its loaders only)

CONFIG = harness.load_json(BENCH, "configs", "kmeans-blobs.json")
REFERENCE = harness.load_module("references", CONFIG["reference"])
GENERATOR = harness.load_module("generators", CONFIG["generator"])


def _blobs(rows=4003, features=6, k=5, seed=0, spread=0.5):
    """``rows`` rows (no multiple of the mesh) of ``k`` blobs that stand
    apart, in no order."""
    r = np.random.default_rng(seed)
    centres = r.uniform(-10, 10, size=(k, features))
    X = centres[r.integers(k, size=rows)] + spread * r.normal(
        size=(rows, features))
    return X.astype(np.float32)


def _brute_weights(X, w, cand):
    """Every row's weight on the candidate nearest to it, in float64, and
    how many rows have a runner-up too close for float32 to tell."""
    d2 = ((X[:, None, :].astype(np.float64) - cand[None, :, :]) ** 2).sum(-1)
    order = np.sort(d2, axis=1)
    close = int((order[:, 1] - order[:, 0] < 1e-4 * order[:, 1]).sum())
    return np.bincount(d2.argmin(axis=1), weights=w,
                       minlength=len(cand)), close


def _sampled(Xs, k=5, key=0, **kw):
    cand, keep, weights, rounds, _, cap, _ = km._sample_candidates(
        Xs, k, jax.random.PRNGKey(key), kw.pop("oversampling_factor", 2),
        kw.pop("init_max_iter", None))
    return np.asarray(cand, np.float64), np.asarray(keep), np.asarray(
        weights, np.float64), int(rounds), cap


def test_weights_are_brute_force_nearest_candidate_counts_with_pad_rows():
    X = _blobs()
    Xs = shard_rows(X)
    assert Xs.data.shape[0] > X.shape[0]  # the mesh padded the rows
    cand, keep, weights, rounds, cap = _sampled(Xs)
    assert keep[0] and 1 < keep.sum() <= 1 + rounds * cap
    assert weights[~keep].sum() == 0 and weights.sum() == X.shape[0]
    brute, close = _brute_weights(X, np.ones(len(X)), cand[keep])
    assert np.abs(weights[keep] - brute).sum() <= 2 * close
    # every candidate is a row of the table, none a pad row
    rows = {r.tobytes() for r in X}
    assert all(c.astype(np.float32).tobytes() in rows for c in cand[keep])


def test_weights_sum_the_sample_weights_of_the_nearest_rows():
    X = _blobs(seed=1)
    w = np.random.default_rng(2).uniform(0.5, 3.0, size=len(X)).astype(
        np.float32)
    w[::7] = 0.0  # rows of no weight are never drawn and weigh nothing
    cand, keep, weights, _, _ = _sampled(
        reweight_rows(shard_rows(X), sample_weight=w), key=3)
    brute, close = _brute_weights(X, w.astype(np.float64), cand[keep])
    np.testing.assert_allclose(weights[keep], brute, rtol=1e-5,
                               atol=3.0 * 2 * close + 1e-3)
    zero = {r.tobytes() for r in X[::7]} - {r.tobytes() for r in X[w > 0]}
    assert not any(c.astype(np.float32).tobytes() in zero for c in cand[keep])


def _avals(jaxpr):
    """Every value a jaxpr makes, sub-jaxprs (loops, shard_map) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_no_intermediate_of_the_init_has_rows_x_total_slots_elements():
    rows, d, cap, max_rounds = 4096, 6, 40, 16
    mh = MeshHolder(get_mesh())
    shards = len(jax.devices())
    x = jnp.zeros((rows, d), jnp.float32)
    v = jnp.zeros((rows,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda *a: km._init_rounds_fn(
            *a, ell=10.0, cap=cap, max_rounds=max_rounds, mesh_holder=mh,
            scatter="segsum"))(
        x, v, v, jnp.zeros((d,)), v, jax.random.PRNGKey(0), jnp.int32(3))
    sizes = [int(np.prod(a.shape)) for a in _avals(jaxpr.jaxpr)
             if hasattr(a, "shape")]
    local = rows // shards
    # the largest is one round's distances, local rows x cap (the table
    # itself is an input); rows x slots would be 16 times that
    assert max(sizes) == local * cap
    assert max(sizes) < local * (1 + max_rounds * cap) // 8


def test_the_compiled_init_moves_no_rows_sized_operand_between_chips():
    """On the 8-device mesh every shard draws, compacts and gathers among
    its own rows: what crosses chips is a round's shards x cap candidate
    rows, phi and the weights; nothing is sorted."""
    Xs = shard_rows(_blobs(rows=8192))
    shards = len(Xs.data.sharding.device_set)
    assert shards == len(jax.devices()) > 1
    mh = MeshHolder(get_mesh())
    key = jax.random.PRNGKey(0)
    first = km._init_first.lower(Xs.data, Xs.mask, key, mesh_holder=mh)
    c0, d2, _ = km._init_first(Xs.data, Xs.mask, key, mesh_holder=mh)
    rounds = km._init_rounds.lower(
        Xs.data, Xs.mask, d2, c0, d2, key, jnp.int32(4), ell=10.0, cap=40,
        max_rounds=8, mesh_holder=mh, scatter="segsum")
    local = Xs.data.shape[0] // shards
    for lowered in (first, rounds):
        hlo = lowered.compile().as_text()
        ops = set(re.findall(r"[ )]([a-z][a-z-]*)\(", hlo))
        assert "all-reduce" in ops and "all-gather" in ops
        assert not ops & {"sort", "all-to-all", "collective-permute"}
        for shape in re.findall(
                r"= \(?([a-z0-9]+\[[0-9,]*\])[^=]*? all-gather\(", hlo):
            dims = [int(n) for n in re.findall(r"\d+", shape.split("[")[1])]
            assert all(n < local for n in dims), shape


def _table(seed, rows, chips):
    params = dict(CONFIG["generator_params"], block_rows=rows // chips,
                  features=10)
    return GENERATOR.make(harness.seed_key(jax, seed), rows, params,
                          harness.row_sharding(jax.devices()[:chips]))


@pytest.mark.parametrize("chips", [1, 8])
def test_fit_agrees_with_the_plain_reference(chips):
    """``KMeans.fit`` against plain Lloyd from the generating centres, by
    the benchmark's own numbers and limits, one device and eight."""
    data = _table(11, 16_000, chips)
    with use_mesh(device_mesh(chips)):
        est = KMeans(**harness.substitute_seed(CONFIG["estimator_args"], 0))
        est.fit(shard_rows(data["X"]))
    assert len(est.labels_.sharding.device_set) == chips
    ref = REFERENCE.build(data, {})
    got = REFERENCE.compare(
        ref, data, harness.fetch_answer(np, est, CONFIG["fetch"]),
        {"labels_": est.labels_})
    assert set(got) == set(CONFIG["limits"])
    for name, limit in CONFIG["limits"].items():
        assert got[name] <= limit, (name, got[name])


def _fit_tree():
    """The last fit's span tree (not ``obs.span_tree()``'s newest root: a
    thread an earlier test file left behind may open roots meanwhile)."""
    roots = [r for r in obs.span_records()
             if r.name == "kmeans.fit" and r.parent_id is None]
    return obs.span_tree(roots[-1])


def _fit_counts(est):
    tree = _fit_tree()
    child = {c["name"]: c for c in tree["children"]}
    return (child["kmeans.init"]["attrs"]["rounds"],
            child["kmeans.init"]["attrs"]["candidates"],
            child["kmeans.lloyd"]["attrs"]["iters"])


def test_two_run_seeds_mirror_each_other_bit_for_bit():
    """The run seed flips feature columns: the same rows are drawn in the
    same rounds, Lloyd runs the same iterations, and the centres come out
    with the signs."""
    fits = []
    for seed in (5, 2**31 + 77):
        data = _table(seed, 8_000, 1)
        est = KMeans(n_clusters=8, random_state=0).fit(shard_rows(data["X"]))
        fits.append((np.asarray(data["X"]), np.asarray(est.cluster_centers_),
                     est.inertia_, _fit_counts(est)))
    (xa, ca, ia, na), (xb, cb, ib, nb) = fits
    signs = np.sign(xa[0] * xb[0])
    assert set(np.unique(signs)) == {-1.0, 1.0}  # the seeds differ
    assert np.array_equal(xa * signs, xb)
    assert np.array_equal(ca * signs, cb) and ia == ib and na == nb


def test_one_compile_serves_every_round_and_the_second_fit():
    Xs = shard_rows(_blobs(rows=2051, seed=4))
    KMeans(n_clusters=5, random_state=1).fit(Xs)
    before = diagnostics.program_report()["totals"]
    est = KMeans(n_clusters=5, random_state=2).fit(Xs)  # other draws
    after = diagnostics.program_report()["totals"]
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]
    assert _fit_counts(est)[0] > 1  # many rounds, one program


def test_span_tree_and_counters():
    def counts():
        c = obs.metrics_snapshot()["counters"]
        return {k: c.get(k, 0) for k in (
            "kmeans.count", "kmeans.init_rounds", "kmeans.candidates",
            "kmeans.lloyd_iters")}

    X = _blobs(rows=1500, seed=6)
    before = counts()
    est = KMeans(n_clusters=5, random_state=0).fit(X)
    tree = _fit_tree()
    assert tree["attrs"] == {"n_clusters": 5, "init": "k-means||",
                             "rows": 1500, "features": 6,
                             "chips": len(jax.devices())}
    assert [c["name"] for c in tree["children"]] == [
        "kmeans.init", "kmeans.lloyd", "kmeans.assign"]
    rounds, candidates, iters = _fit_counts(est)
    assert iters == est.n_iter_ >= 1
    assert tree["children"][0]["attrs"]["cap"] == 40
    assert set(tree["children"][1]["attrs"]) == {"iters", "tol_anchor_share"}
    assert 0 <= tree["children"][1]["attrs"]["tol_anchor_share"] < 0.01
    assert 1 < candidates <= 1 + rounds * 40
    after = counts()
    assert {k: after[k] - before[k] for k in after} == {
        "kmeans.count": 1, "kmeans.init_rounds": rounds,
        "kmeans.candidates": candidates, "kmeans.lloyd_iters": iters}


def test_init_max_iter_bounds_the_rounds():
    Xs = shard_rows(_blobs(rows=3000, seed=7))
    assert _sampled(Xs, init_max_iter=3)[3] == 3
    assert _sampled(Xs)[3] > 3
    est = KMeans(n_clusters=5, init_max_iter=2, random_state=0).fit(Xs)
    assert _fit_counts(est)[0] == 2


def test_fewer_candidates_than_clusters_are_padded_with_real_rows():
    """The degenerate branch: one round on a handful of rows leaves fewer
    candidates than clusters, and the init still hands back k centres."""
    X = _blobs(rows=24, k=3, seed=8)
    cand, keep, *_ = _sampled(shard_rows(X), k=12, init_max_iter=1)
    assert keep.sum() <= 12
    est = KMeans(n_clusters=12, init_max_iter=1, random_state=0).fit(X)
    assert est.cluster_centers_.shape == (12, 6)
    assert np.isfinite(np.asarray(est.cluster_centers_)).all()
    assert est.labels_.shape == (24,)


# -- the fold at the width of the round's draw (ISSUE 31) -------------------
#
# The tables below hold small whole numbers: every product and partial sum
# of a squared distance is then exact in float32, in whatever order a
# backend's matrix product adds it up.  (The CPU's dot rounds a column
# differently at another width of the right-hand side, so on a table of
# real numbers two widths agree to an ulp and not to the bit here; on the
# chip a slot's distance contracts K whole at any width, PERF.md PR 31.)

_ELL, _CAP = 16.0, 64  # the benchmark's cell: the widths 16 / 24 / 32 / 64


def _whole_numbers(rows, features, seed):
    return np.random.default_rng(seed).integers(
        -8, 9, size=(rows, features)).astype(np.float32)


@pytest.mark.parametrize("ell, cap, widths", [
    (16.0, 64, (16, 24, 32, 64)),  # the benchmark's cell: k = 8
    (200.0, 800, (200, 304, 400, 800)),  # k = 100
    (10.0, 40, (16, 24, 40)),
    (4.0, 16, (8, 16)),           # ceil8 of ell, 1.5 ell, 2 ell: merged
    (2.0, 8, (8,)),               # a cap of 8 keeps one branch
    (16.0, 20, (16, 20)),         # cap cut to a small shard's rows
    (16.0, 5, (5,)),
    (2.5, 10, (8, 10)),           # a fractional oversampling factor
])
def test_fold_widths_follow_ell_and_never_pass_cap(ell, cap, widths):
    assert km._fold_widths(ell, cap) == widths


@functools.cache
def _fold_on(chips, widths):
    """``_fold_candidates`` as one jitted per-shard program over ``chips``
    devices (compiled once for all the counts); also hands back the width
    every shard chose."""
    mesh = device_mesh(chips)
    row_ax = km.data_axes(mesh)
    spec = km.P

    def local(x, x_norm, rows, valid, d2, nearest, base):
        d2, nearest, width = km._fold_candidates(
            x, x_norm, rows, valid, d2, nearest, base, widths)
        return d2, nearest, width[None]

    return jax.jit(km._shard_map(
        local, mesh,
        in_specs=(spec(row_ax, None), spec(row_ax), spec(), spec(),
                  spec(row_ax), spec(row_ax), spec()),
        out_specs=(spec(row_ax), spec(row_ax), spec(row_ax))))


def _fold_case(count):
    """A table, ``_CAP`` slots of which the first ``count`` hold one of
    its rows, and a carried state some rows of which no slot improves."""
    X = _whole_numbers(2048, 6, seed=20)
    rows = np.zeros((_CAP, 6), np.float32)
    rows[:count] = X[np.random.default_rng(21).choice(
        len(X), size=count, replace=False)]
    valid = np.arange(_CAP) < count
    d2 = ((X - X[7]) ** 2).sum(axis=1)
    d2[::3] = 1.0  # nearer than any slot but the row's own
    nearest = np.arange(len(X), dtype=np.int32) % 5
    return X, rows, valid, d2.astype(np.float32), nearest


#: 0, 1, and each width with the count that first passes it, up to cap
_COUNTS = [0, 1, 16, 17, 24, 25, 32, 33, 64]


@pytest.mark.parametrize("count", _COUNTS)
def test_narrowed_fold_equals_the_cap_wide_fold_bit_for_bit(count):
    widths = km._fold_widths(_ELL, _CAP)
    X, rows, valid, d2, nearest = _fold_case(count)
    args = (X, (X * X).sum(axis=1), rows, valid, d2, nearest, np.int32(81))
    narrow = [np.asarray(a) for a in _fold_on(1, widths)(*args)]
    wide = [np.asarray(a) for a in _fold_on(1, (_CAP,))(*args)]
    assert narrow[2].tolist() == [min(w for w in widths if w >= count)]
    assert wide[2].tolist() == [_CAP]
    assert np.array_equal(narrow[0], wide[0])
    assert np.array_equal(narrow[1], wide[1])
    # and both are the brute-force fold over the filled slots
    new = ((X[:, None, :] - rows[None, :count, :]) ** 2).sum(-1)
    least = new.min(axis=1, initial=np.inf)
    closer = least < d2
    assert np.array_equal(narrow[0], np.where(closer, least, d2))
    if count:  # ties go to the first slot, here as in numpy
        assert np.array_equal(narrow[1][closer],
                              81 + new[closer].argmin(axis=1))
    assert np.array_equal(narrow[1][~closer], nearest[~closer])
    assert closer.any() == (count > 0) and not closer.all()


@pytest.mark.parametrize("count", _COUNTS)
def test_two_shards_and_one_shard_take_the_same_branch(count):
    """The count is the same on every shard after the all-gather, so
    every shard takes the one branch a single shard would."""
    widths = km._fold_widths(_ELL, _CAP)
    X, rows, valid, d2, nearest = _fold_case(count)
    args = (X, (X * X).sum(axis=1), rows, valid, d2, nearest, np.int32(81))
    one = [np.asarray(a) for a in _fold_on(1, widths)(*args)]
    two = [np.asarray(a) for a in _fold_on(2, widths)(*args)]
    assert two[2].tolist() == one[2].tolist() * 2
    assert np.array_equal(one[0], two[0]) and np.array_equal(one[1], two[1])


def _round_counts(keep, rounds, cap):
    """How many slots each round filled, from the buffer's valid flags."""
    return [int(keep[1 + r * cap: 1 + (r + 1) * cap].sum())
            for r in range(rounds)]


#: a key under which three of the 14 rounds on ``_whole_numbers(2003, 4,
#: 12)`` draw 9 rows where ell is 4 (a round in fifty does)
_OVERFLOW_KEY = 10


def test_a_round_that_draws_more_than_w2_takes_the_cap_branch(monkeypatch):
    """... and the program returns what the parent's does, whose every
    fold is ``cap`` wide: the same candidates in the same slots, the same
    weights."""
    Xs = shard_rows(_whole_numbers(2003, 4, seed=12))
    key = jax.random.PRNGKey(_OVERFLOW_KEY)
    cand, keep, weights, rounds, slots, cap, _ = km._sample_candidates(
        Xs, 2, key, 2, None)
    widths = km._fold_widths(4.0, cap)
    assert (cap, widths) == (16, (8, 16))
    counts = _round_counts(np.asarray(keep), int(rounds), cap)
    assert max(counts) > widths[-2]  # the draw the test is made for
    assert int(slots) == sum(
        min(w for w in widths if w >= c) for c in counts)
    assert int(rounds) * widths[0] < int(slots) < int(rounds) * cap

    def cap_wide(*args, **static):  # a new function: traced anew
        return km._init_rounds_fn(*args, **static)

    monkeypatch.setattr(km, "_fold_widths", lambda ell, cap: (cap,))
    monkeypatch.setattr(km, "_init_rounds", jax.jit(
        cap_wide, static_argnames=(
            "ell", "cap", "max_rounds", "mesh_holder", "scatter")))
    parent = km._sample_candidates(Xs, 2, key, 2, None)
    assert int(parent[4]) == int(parent[3]) * cap  # every fold cap wide
    for got, want in zip((cand, keep, weights, rounds), parent):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_slots_on_the_span_and_in_the_registry_are_the_widths_summed():
    def counter():
        return obs.metrics_snapshot()["counters"].get("kmeans.init_slots", 0)

    Xs = shard_rows(_blobs(rows=1500, seed=6))
    before = counter()
    KMeans(n_clusters=5, random_state=3).fit(Xs)
    attrs = next(c for c in _fit_tree()["children"]
                 if c["name"] == "kmeans.init")["attrs"]
    _, keep, _, rounds, slots, cap, _ = km._sample_candidates(
        Xs, 5, jax.random.PRNGKey(3), 2, None)  # the fit's own draws
    widths = km._fold_widths(10.0, cap)
    assert (cap, widths, attrs["cap"]) == (40, (16, 24, 40), 40)
    by_hand = sum(min(w for w in widths if w >= c) for c in _round_counts(
        np.asarray(keep), int(rounds), cap))
    assert attrs["slots"] == int(slots) == by_hand == counter() - before
    assert attrs["rounds"] == int(rounds)
    assert attrs["rounds"] * widths[0] <= by_hand < attrs["rounds"] * cap


def test_first_selected_finds_the_first_true_entries():
    r = np.random.default_rng(9)
    for n, k, p in ((5000, 7, 0.01), (1024, 4, 0.0), (3000, 16, 0.5),
                    (10, 8, 0.3)):
        sel = r.random(n) < p
        idx, found = km._first_selected(jnp.asarray(sel), k)
        want = np.flatnonzero(sel)[:k]
        assert int(found.sum()) == len(want)
        assert np.array_equal(np.asarray(idx)[:len(want)], want)


def test_rows_are_drawn_on_32_bits_not_on_a_float32_uniform():
    """A round's p is about ell / rows: under 2^-23, where a float32
    uniform cannot tell one probability from the next."""
    bits = jnp.asarray([0, 42, 43, 2**31, 2**32 - 1], jnp.uint32)
    p = jnp.float32(43 * 2.0 ** -32)  # 1e-8: the 43 smallest of 2^32
    assert np.asarray(km._drawn(bits, p)).tolist() == [
        True, True, False, False, False]
    assert not np.asarray(km._drawn(bits, jnp.float32(0.0))).any()
    assert np.asarray(km._drawn(bits, jnp.float32(1.0))).all()
    assert np.asarray(km._drawn(bits, jnp.float32(7.5))).all()
    half = np.asarray(km._drawn(
        jax.random.bits(jax.random.PRNGKey(0), (200_000,)), jnp.float32(0.5)))
    assert abs(half.mean() - 0.5) < 0.01
    u = np.asarray(km._unit_interval(bits))
    assert 0 < u[0] < u[1] < 2e-8 and u[-1] <= 1.0


def test_first_candidate_is_drawn_by_weight():
    X = (np.arange(40, dtype=np.float32)[:, None] * np.ones((1, 3), np.float32))
    w = np.where(np.arange(40) < 20, 3.0, 1.0).astype(np.float32)
    w[5] = 0.0
    Xw = reweight_rows(shard_rows(X), sample_weight=w)
    mh = MeshHolder(get_mesh())
    firsts = [int(km._init_first(Xw.data, Xw.mask, jax.random.PRNGKey(i),
                                 mesh_holder=mh)[0][0]) for i in range(400)]
    assert 5 not in firsts and len(set(firsts)) > 30
    share = np.mean(np.asarray(firsts) < 20)  # 57 of 77 by weight
    assert abs(share - 57 / 77) < 0.08


def test_histogram_over_many_buckets_equals_segment_sum():
    from dask_ml_tpu.ops import bucket_sum

    r = np.random.default_rng(10)
    ids = jnp.asarray(r.integers(0, 2049, size=20_000), jnp.int32)
    w = jnp.asarray(r.uniform(0, 2, size=20_000), jnp.float32)
    two = bucket_sum(w, ids, 2049, strategy="onehot2",
                     precision=jax.lax.Precision.HIGHEST)
    seg = bucket_sum(w, ids, 2049, strategy="segsum")
    assert two.shape == seg.shape == (2049,)
    np.testing.assert_allclose(np.asarray(two), np.asarray(seg), rtol=1e-5)


def test_lloyd_sums_offsets_from_the_current_centre():
    """The per-cluster reduce adds up rows' offsets from their current
    centre, so a blob far from the origin keeps a mean exact to float32."""
    r = np.random.default_rng(11)
    X = (1000.0 + r.normal(size=(40_000, 4))).astype(np.float32)
    est = KMeans(n_clusters=1, init=X[:1].copy(), max_iter=5).fit(X)
    exact = X.astype(np.float64).mean(axis=0)
    assert np.abs(np.asarray(est.cluster_centers_)[0] - exact).max() < 2e-4


# ---------------------------------------------------------------------------
# ISSUE 37: the tolerance from one read of the table, the last assignment
# on the norms k-means|| made
# ---------------------------------------------------------------------------

_CHIPS = [1, 2, 8]


def _sample_stride(rows, chips):
    """The stride ``_tol_fn`` samples a shard's rows at."""
    return km._sample_stride(-(-rows // chips),
                             max(km._TOL_SAMPLE // chips, 1))


def _periodic(rows=8195, features=6, k=8, seed=2):
    """Blobs laid out as the benchmark's are: row ``i`` belongs to blob
    ``i % k``.  ``rows // 1024`` is 8 on every mesh of the file, the
    period itself: a stride of 8 would sample one blob."""
    r = np.random.default_rng(seed)
    centres = r.uniform(-10, 10, size=(k, features))
    return (centres[np.arange(rows) % k]
            + r.normal(size=(rows, features))).astype(np.float32)


def _tol_tables(chips):
    """name -> (table, whether a good anchor is asked of it)."""
    X = _blobs()  # 4003 rows: pad rows on 2 and 8 shards, some sampled
    sd = X.std(axis=0)
    outlier = X.copy()  # one sampled row stands 1e3 deviations away
    outlier[3 * _sample_stride(len(X), chips)] += 1e3 * sd
    return {
        "blobs": (X, True),
        "offset": ((X + 1e4 * sd).astype(np.float32), True),
        "sorted": (X[np.argsort(X[:, 0])], True),
        "periodic": (_periodic(), True),
        "outlier": (outlier, False),
        # fewer real rows than the sample asks for: every row is sampled,
        # the pad rows among them
        "few_rows": (X[:301], True),
    }


@pytest.mark.parametrize("chips", _CHIPS)
@pytest.mark.parametrize(
    "table", ["blobs", "offset", "sorted", "periodic", "outlier", "few_rows"])
def test_the_tolerance_is_sklearns_from_one_pass(table, chips):
    X, anchored = _tol_tables(chips)[table]
    with use_mesh(device_mesh(chips)):
        Xs = shard_rows(X)
        assert Xs.data.shape[0] > len(X) or chips == 1  # pad rows
        tol, share = jax.device_get(km._tol(
            Xs.data, Xs.mask, 1e-4, mesh_holder=MeshHolder(get_mesh())))
    want = 1e-4 * np.var(X.astype(np.float64), axis=0).mean()
    assert tol.dtype == np.float32
    assert abs(tol / want - 1) < 1e-5
    if anchored:
        assert 0 <= share < 0.01
    else:  # the sample's mean moved by a deviation of the other rows
        assert 0 <= share < 1


@pytest.mark.parametrize("rows, want, stride", [
    (25_000_000, 1024, 24_413),  # the benchmark's cell: 24,414 is even
    (8195, 1024, 7), (1025, 128, 7), (4003, 1024, 3), (501, 128, 3),
    (2048, 1024, 2), (2047, 1024, 1), (300, 1024, 1), (38, 128, 1),
])
def test_the_sample_stride_is_prime(rows, want, stride):
    assert km._sample_stride(rows, want) == stride


def test_a_far_anchor_reads_a_large_share():
    """What ``tol_anchor_share`` is for: where the sample's mean stands
    far from the table's (half the sampled rows, and no other, 300
    deviations off: the sample's mean 150, the table's 50) it says so."""
    X = _blobs()
    X[: len(X) // 2: _sample_stride(len(X), 1)] += 300 * X.std(axis=0)
    with use_mesh(device_mesh(1)):
        Xs = shard_rows(X)
        tol, share = km._tol(Xs.data, Xs.mask, 1e-4,
                             mesh_holder=MeshHolder(get_mesh()))
    assert 0.25 < float(share) < 1
    want = 1e-4 * np.var(X.astype(np.float64), axis=0).mean()
    assert abs(float(tol) / want - 1) < 1e-5  # eps * (1 + 0.8) all the same


@pytest.mark.parametrize("chips", _CHIPS)
def test_sample_weight_does_not_move_the_tolerance(chips, monkeypatch):
    queued, real = [], km._tol

    def recording(x, mask, tol, **static):
        queued.append(real(x, mask, tol, **static))
        return queued[-1]

    X = _blobs(rows=2003, seed=3)
    w = np.random.default_rng(4).uniform(0.0, 5.0, size=len(X))
    w[::7] = 0.0
    monkeypatch.setattr(km, "_tol", recording)
    with use_mesh(device_mesh(chips)):
        KMeans(n_clusters=5, random_state=0).fit(X)
        KMeans(n_clusters=5, random_state=0).fit(X, sample_weight=w)
    (plain, _), (weighted, _) = queued
    want = 1e-4 * np.var(X.astype(np.float64), axis=0).mean()
    assert float(plain) == float(weighted)
    assert abs(float(plain) / want - 1) < 1e-5


@pytest.mark.parametrize("chips", _CHIPS)
def test_assign_on_carried_norms_is_assign(chips):
    """Labels bit for bit, the inertia within an ulp: the same expression
    on the same operands, less the recomputed ``|x|^2``."""
    X = _blobs(rows=4003, seed=8)
    centers = jnp.asarray(X[:: len(X) // 5][:5] + 0.25)
    with use_mesh(device_mesh(chips)):
        Xs = shard_rows(X)
        labels, inertia = km._assign(Xs.data, Xs.mask, centers)
        on_norms = km._assign(Xs.data, Xs.mask, centers,
                              km._row_norms(Xs.data))
    assert np.array_equal(np.asarray(labels), np.asarray(on_norms[0]))
    assert len(np.unique(np.asarray(labels)[: len(X)])) == 5
    assert abs(float(inertia) - float(on_norms[1])) <= np.spacing(
        np.float32(inertia))


def _gaps_from(data, est, start):
    """The benchmark's numbers for ``est`` against plain Lloyd from
    ``start`` (the fit's own: a local optimum is then the reference's
    too)."""
    centres, _ = REFERENCE.lloyd(data["X"], start)
    inertia = REFERENCE.sweep(data["X"], centres)[2]
    ref = {"centers": centres,
           "spread": float(np.sqrt(inertia / data["X"].shape[0]))}
    return REFERENCE.compare(
        ref, data, harness.fetch_answer(np, est, CONFIG["fetch"]),
        {"labels_": est.labels_})


def _norms_given(monkeypatch):
    """How many operands each ``kmeans.assign`` of the test was given."""
    given = []
    real = km._assign

    def recording(*operands):
        given.append(len(operands))
        return real(*operands)

    monkeypatch.setattr(km, "_assign", recording)
    return given


@pytest.mark.parametrize("init", ["array", "random", "resumed"])
def test_a_fit_that_carries_no_norms_agrees_with_the_plain_reference(
        init, monkeypatch, tmp_path):
    from dask_ml_tpu.core.prng import as_key
    from dask_ml_tpu.resilience import (FaultInjected, FitCheckpoint,
                                        fault_plan)

    data = _table(13, 16_000, 8)
    Xs = shard_rows(data["X"])
    given = _norms_given(monkeypatch)
    if init == "array":
        start = REFERENCE.far_start(data["X"], 8, head=16_000)
        est = KMeans(n_clusters=8, init=np.asarray(start, np.float32))
    elif init == "random":
        # a blob split between two random rows settles slowly: down to
        # the fixed point the reference walks to, not to the threshold
        est = KMeans(n_clusters=8, init="random", random_state=5, tol=0.0)
        start = np.asarray(est._init_centers(Xs, as_key(5)))
    else:
        path = str(tmp_path / "killed.pkl")

        def make():
            return KMeans(n_clusters=8, random_state=0, tol=0.0, max_iter=6,
                          fit_checkpoint=FitCheckpoint(path, every_n_iters=1))

        with fault_plan() as plan:
            plan.inject("step", at_call=2)
            with pytest.raises(FaultInjected):
                make().fit(Xs)
        est = make()
        start = np.asarray(est.fit_checkpoint.load_if_matches(est)[1][
            "centers"])
    est.fit(Xs)
    assert given == [3]  # the program predict and score run
    got = _gaps_from(data, est, start)
    for name, limit in CONFIG["limits"].items():
        assert got[name] <= limit, (name, got[name])


def test_a_kmeans_parallel_fit_assigns_on_the_inits_norms(monkeypatch):
    given = _norms_given(monkeypatch)
    X = _blobs(rows=1500, seed=6)
    est = KMeans(n_clusters=5, random_state=0).fit(X)
    assert given == [4]
    Xs = shard_rows(X)
    labels, inertia = km._assign(Xs.data, Xs.mask, est.cluster_centers_)
    assert np.array_equal(np.asarray(est.labels_),
                          np.asarray(labels)[: len(X)])
    assert abs(est.inertia_ - float(inertia)) <= np.spacing(
        np.float32(inertia))
