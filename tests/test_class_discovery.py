"""Class discovery on device labels: ``core.sharded.masked_unique`` finds
the distinct values of the real rows by a bounded scan in one program and
sorts only what the scan cannot hold (more than ``UNIQUE_CAP`` values, a
NaN).  Every case is held to ``np.unique`` of the real rows on the
8-device mesh of ``conftest.py``."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu import obs
from dask_ml_tpu.core.sharded import (
    UNIQUE_CAP, _unique_scan, masked_unique, shard_rows)


def _labels(dtype, k, rows=1003, seed=0):
    """``rows`` labels (no multiple of the mesh) of exactly ``k`` distinct
    values, negative ones among them, in no order."""
    r = np.random.default_rng(seed)
    values = (r.permutation(4 * UNIQUE_CAP)[:k] - 2 * UNIQUE_CAP) * 3
    y = r.choice(values, size=rows)
    y[:k] = values
    return r.permutation(y).astype(dtype)


def _sharded(y):
    s = shard_rows(jnp.asarray(y))
    return s.data, s.mask


def _found(y):
    return masked_unique(*_sharded(y))


def _counts():
    c = obs.metrics_snapshot()["counters"]
    return c.get("classes.scan", 0), c.get("classes.sort", 0)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("k", [1, 2, 3, UNIQUE_CAP, UNIQUE_CAP + 1])
def test_equals_numpy_unique(dtype, k):
    y = _labels(dtype, k)
    assert len(y) % len(jax.devices()) != 0  # pad rows exist
    before = _counts()
    got = _found(y)
    want = np.unique(y)
    assert got.dtype == want.dtype and len(want) == k
    np.testing.assert_array_equal(got, want)
    scan, sort = np.subtract(_counts(), before)
    assert (scan, sort) == ((1, 0) if k <= UNIQUE_CAP else (0, 1))


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_pad_rows_mint_no_class(dtype):
    """The pad rows' 0 is below one label and above the other, and is
    neither found nor counted."""
    y = np.where(np.arange(13) % 2 == 0, -5, 7).astype(dtype)
    s = shard_rows(jnp.asarray(y))
    assert s.data.shape[0] > 13 and float(s.data[-1]) == 0
    np.testing.assert_array_equal(masked_unique(s.data, s.mask), [-5, 7])


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_row_zero_need_not_be_real(dtype):
    """Row 0 masked out and holding a value no real row has (the old
    code remapped pad rows to ``data[0]``)."""
    data = jnp.asarray(np.array([99, 1, 2, 1, 2, 2, 1, 2], dtype))
    mask = jnp.asarray(np.array([0, 1, 1, 1, 1, 1, 1, 1], np.float32))
    np.testing.assert_array_equal(masked_unique(data, mask), [1, 2])


@pytest.mark.parametrize("top", [
    np.float32(np.inf), np.finfo(np.float32).max,
    np.iinfo(np.int32).max, np.iinfo(np.int32).min])
def test_the_dtypes_extremes_are_classes(top):
    y = np.array([top, 1, top, 1, 1, 3, 3] * 3, dtype=type(top))
    before = _counts()
    np.testing.assert_array_equal(_found(y), np.unique(y))
    assert tuple(np.subtract(_counts(), before)) == (1, 0)


def test_nan_label_falls_back_to_the_sort():
    y = np.array([2, np.nan, 1, 2, np.nan, 1, 1], np.float32)
    before = _counts()
    got = _found(y)
    np.testing.assert_array_equal(got, np.unique(y))  # [1, 2, nan]
    assert tuple(np.subtract(_counts(), before)) == (0, 1)


def test_nan_in_a_pad_row_is_no_label():
    data = jnp.asarray(np.array([1, 2, np.nan, np.nan], np.float32))
    mask = jnp.asarray(np.array([1, 1, 0, 0], np.float32))
    before = _counts()
    np.testing.assert_array_equal(masked_unique(data, mask), [1, 2])
    assert tuple(np.subtract(_counts(), before)) == (1, 0)


def test_bool_labels_keep_their_dtype():
    y = np.arange(11) % 3 == 0
    got = _found(y)
    assert got.dtype == np.bool_
    np.testing.assert_array_equal(got, [False, True])


def test_no_real_row_finds_nothing():
    got = masked_unique(jnp.zeros(8, jnp.float32), jnp.zeros(8, jnp.float32))
    assert got.shape == (0,) and got.dtype == np.float32


def _classes_span(y):
    """Fit on device labels; the fit's ``glm.classes`` span."""
    from dask_ml_tpu.linear_model import LogisticRegression

    r = np.random.default_rng(1)
    X = r.normal(size=(len(y), 3)).astype(np.float32)
    est = LogisticRegression(solver="lbfgs", max_iter=2).fit(
        shard_rows(X), shard_rows(jnp.asarray(y)))
    tree = obs.span_tree()
    assert tree["name"] == "glm.fit"
    span = next(c for c in tree["children"] if c["name"] == "glm.classes")
    return est, span["attrs"]


@pytest.mark.parametrize("k,path", [(2, "scan"), (UNIQUE_CAP + 1, "sort")])
def test_fit_says_which_path_found_its_classes(k, path):
    y = _labels(np.float32, k, rows=max(1003, 2 * k))
    before = _counts()
    if path == "scan":
        est, attrs = _classes_span(y)
        np.testing.assert_array_equal(est.classes_, np.unique(y))
    else:
        # the sort's path is read where the classes are found: a fit of
        # UNIQUE_CAP + 1 classes would compile a solve that wide
        with obs.span("glm.classes") as span:
            found = masked_unique(*_sharded(y), span)
        np.testing.assert_array_equal(found, np.unique(y))
        attrs = span.attrs
    assert attrs["path"] == path
    assert attrs["scan_steps"] == min(k, UNIQUE_CAP)
    scan, sort = np.subtract(_counts(), before)
    assert (scan, sort) == ((1, 0) if path == "scan" else (0, 1))


def test_three_class_fit_scans_three_steps():
    y = _labels(np.int32, 3, rows=301)
    est, attrs = _classes_span(y)
    np.testing.assert_array_equal(est.classes_, np.unique(y))
    assert attrs == {"classes": 3, "path": "scan", "scan_steps": 3}


def test_host_labels_touch_neither_path():
    from dask_ml_tpu.linear_model import LogisticRegression

    r = np.random.default_rng(2)
    X = r.normal(size=(64, 3)).astype(np.float32)
    before = _counts()
    LogisticRegression(solver="lbfgs", max_iter=2).fit(X, np.arange(64) % 2)
    assert _counts() == before


def test_the_scan_gathers_and_sorts_nothing():
    """Compiled for the row-sharded input the scan reduces per shard and
    all-reduces scalars: no label crosses a shard, nothing is sorted."""
    data, mask = _sharded(_labels(np.float32, 2, rows=4096))
    assert len(data.sharding.device_set) == len(jax.devices()) > 1
    hlo = _unique_scan.lower(data, mask).compile().as_text()
    # the operations by their opcodes ("sort" is in this test's name,
    # which the HLO's stack frames carry)
    ops = set(re.findall(r"[ )]([a-z][a-z-]*)\(", hlo))
    assert {"all-reduce", "while", "reduce"} <= ops
    assert not ops & {"all-gather", "sort", "all-to-all",
                      "collective-permute", "scatter", "gather"}


@pytest.mark.parametrize("site", ["sweep", "fold", "blockwise", "metrics"])
def test_every_device_site_calls_the_helper(site):
    """The four other places that held the two lines find their classes
    through the helper: each counts a scan and keeps its answer."""
    r = np.random.default_rng(3)
    X = r.normal(size=(203, 4)).astype(np.float32)
    yh = np.where(X[:, 0] + 0.1 * r.normal(size=203) > 0, 4, -2)
    y = shard_rows(jnp.asarray(yh.astype(np.float32)))
    before = _counts()
    if site == "sweep":
        from dask_ml_tpu.linear_model import LogisticRegression

        _, classes, _ = LogisticRegression(
            solver="lbfgs", max_iter=2)._sweep_fit_binary(
                shard_rows(X), y, [0.1, 1.0])
        np.testing.assert_array_equal(classes, [-2, 4])
        want = 1
    elif site == "fold":
        from dask_ml_tpu.model_selection._search import _fold_classes_ok

        assert _fold_classes_ok(y, y)
        three = shard_rows(jnp.asarray((np.arange(203) % 3).astype(np.float32)))
        assert not _fold_classes_ok(three, y)
        assert not _fold_classes_ok(y, three)
        want = 3
    elif site == "blockwise":
        from dask_ml_tpu.ensemble import BlockwiseVotingClassifier
        from dask_ml_tpu.linear_model import SGDClassifier

        est = BlockwiseVotingClassifier(
            SGDClassifier(max_iter=2, random_state=0)).fit(shard_rows(X), y)
        np.testing.assert_array_equal(est.classes_, [-2, 4])
        want = 1
    else:
        from dask_ml_tpu.metrics import precision_score

        pred = shard_rows(jnp.asarray(np.where(X[:, 0] > 0, 4, -2)
                                      .astype(np.float32)))
        got = precision_score(y, pred, average=None)
        assert got.shape == (2,)
        want = 2  # true and predicted labels
    scan, sort = np.subtract(_counts(), before)
    assert sort == 0 and scan >= want
