"""``Incremental(SGDClassifier)`` on a table resident on the device: a
``partial_fit`` pass as ONE program over the table's blocks
(``linear_model/_sgd_pass.py``), held to the host stream of the same
blocks through ``partial_fit`` on numpy arrays, to the step's equations
written out in float64, and to what it promises: no table byte on the
host, one compile for any number of passes, a span for each pass."""

import numpy as np
import pytest

from dask_ml_tpu import _partial, diagnostics, obs
from dask_ml_tpu.core import device_mesh, shard_rows, use_mesh
from dask_ml_tpu.core import sharded
from dask_ml_tpu.linear_model import SGDClassifier, SGDRegressor
from dask_ml_tpu.linear_model import _sgd_pass
from dask_ml_tpu.wrappers import Incremental

ROWS, CHUNK = 1003, 128  # seven whole blocks and one of 107 rows


def _table(rows=ROWS, features=5, classes=2, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    if classes == 2:
        y = X @ rng.normal(size=features) + rng.logistic(size=rows) > 0
    else:
        y = np.argmax(X[:, :classes] + rng.gumbel(size=(rows, classes)),
                      axis=1)
    return X, y.astype(np.float32)


def _blocks(rows, chunk, shuffle, random_state):
    """The blocks and their order as ``_partial.fit`` states them."""
    spans = list(_partial._row_chunks(rows, chunk))
    if shuffle:
        np.random.RandomState(random_state).shuffle(spans)
    return spans


def _host_stream(model, X, y, passes, *, shuffle, random_state=0,
                 chunk=CHUNK, **fit_kwargs):
    """``partial_fit`` on numpy blocks, one at a time, in that order."""
    for _ in range(passes):
        for lo, hi in _blocks(len(X), chunk, shuffle, random_state):
            model.partial_fit(X[lo:hi], y[lo:hi], **fit_kwargs)
    return model


def _pass_roots():
    return [r for r in obs.span_records()
            if r.name == "incremental.pass" and r.parent_id is None]


def _close(a, b):
    scale = max(float(np.abs(b).max()), 1e-30)
    assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) <= 2e-6 * scale


@pytest.mark.parametrize("chips", [1, 3, 8])
@pytest.mark.parametrize("shuffle", [False, True])
@pytest.mark.parametrize("n_classes", [2, 3])
def test_resident_pass_is_the_host_stream(chips, shuffle, n_classes):
    """Two passes, the state carried between them, a short last block,
    blocks that straddle the shards (1003 rows on 3 and 8 chips): the
    resident pass's answer is the host stream's over the same blocks."""
    X, y = _table(classes=n_classes)
    classes = np.unique(y)
    with use_mesh(device_mesh(chips)):
        sX, sy = shard_rows(X), shard_rows(y)
        resident = SGDClassifier()
        for _ in range(2):
            _partial.fit(resident, sX, sy, chunk_size=CHUNK,
                         shuffle_blocks=shuffle, random_state=0,
                         classes=classes)
    host = _host_stream(SGDClassifier(), X, y, 2, shuffle=shuffle,
                        classes=classes)
    assert resident.t_ == host.t_ == 2 * 8
    _close(resident.coef_, host.coef_)
    _close(resident.intercept_, host.intercept_)
    np.testing.assert_array_equal(resident.classes_, host.classes_)


@pytest.mark.parametrize("chips", [1, 8])
def test_resident_regressor_is_the_host_stream(chips):
    rng = np.random.RandomState(1)
    X = rng.normal(size=(ROWS, 4)).astype(np.float32)
    y = (X @ rng.normal(size=4) + 0.1 * rng.normal(size=ROWS)).astype(
        np.float32)
    with use_mesh(device_mesh(chips)):
        resident = _partial.fit(SGDRegressor(), shard_rows(X), shard_rows(y),
                                chunk_size=CHUNK, shuffle_blocks=True,
                                random_state=4)
    host = _host_stream(SGDRegressor(), X, y, 1, shuffle=True,
                        random_state=4)
    assert resident.t_ == host.t_ == 8
    _close(resident.coef_, host.coef_)
    _close(resident.intercept_, host.intercept_)


def test_resident_pass_follows_the_equations():
    """The step written out in float64: for block b with real rows R_b,
    m = X w + c; g_w = mean over R_b of -s sigma(-s m) x + alpha w; g_c
    the same without x or the penalty; eta_t = 1 / (alpha (t0 + t)),
    t0 = 1 / (alpha eta0); w -= eta_t g_w, c -= eta_t g_c, t += 1: one
    step a block, in the shuffled order, the state carried across
    calls."""
    X, y = _table(rows=2000, features=6, seed=3)
    alpha, eta0 = 1e-3, 0.05
    with use_mesh(device_mesh(1)):
        inc = Incremental(SGDClassifier(alpha=alpha, eta0=eta0),
                          shuffle_blocks=True, random_state=7,
                          chunk_size=300)
        inc.fit(shard_rows(X), shard_rows(y), classes=[0.0, 1.0])
        inc.partial_fit(shard_rows(X), shard_rows(y))
    w, c, t = np.zeros(6), 0.0, 0
    t0 = 1.0 / (alpha * eta0)
    Xd, s = X.astype(np.float64), np.where(y == 1.0, 1.0, -1.0)
    for _ in range(2):
        for lo, hi in _blocks(2000, 300, True, 7):
            m = Xd[lo:hi] @ w + c
            r = -s[lo:hi] / (1.0 + np.exp(s[lo:hi] * m))
            eta = 1.0 / (alpha * (t0 + t))
            w, c = (w - eta * (Xd[lo:hi].T @ r / (hi - lo) + alpha * w),
                    c - eta * r.mean())
            t += 1
    assert inc.estimator_.t_ == t == 14
    np.testing.assert_allclose(inc.estimator_.coef_[0], w, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(inc.estimator_.intercept_[0], c, rtol=2e-5,
                               atol=2e-6)


def test_no_table_byte_reaches_the_host(monkeypatch):
    """On the resident path nothing is unsharded: ``Incremental.fit`` and
    ``partial_fit`` train, every pass's span reads ``table_host_mb`` 0,
    and the labels' check waits on the device once for these labels."""

    def refuse(x):
        raise AssertionError("a resident pass brought a table to the host")

    monkeypatch.setattr(_partial, "unshard", refuse)
    monkeypatch.setattr(sharded, "unshard", refuse)
    X, y = _table(rows=1500)
    with use_mesh(device_mesh(1)):
        sX, sy = shard_rows(X), shard_rows(y)
        inc = Incremental(SGDClassifier(), chunk_size=256, random_state=0)
        inc.fit(sX, sy, classes=[0.0, 1.0])
        for _ in range(2):
            inc.partial_fit(sX, sy)
    spans = _pass_roots()[-3:]
    assert [s.attrs["table_host_mb"] for s in spans] == [0.0] * 3
    assert [s.attrs["host_waits"] for s in spans] == [1, 0, 0]
    assert inc.estimator_.t_ == 3 * 6


def test_forty_passes_compile_the_pass_once():
    """The state a pass leaves is the state the next takes: one compile
    of ``sgd.pass`` for 40 passes, and a span each with its blocks."""
    X, y = _table(rows=777)
    with use_mesh(device_mesh(1)):
        sX, sy = shard_rows(X), shard_rows(y)
        before = diagnostics.program_report()["programs"].get(
            "sgd.pass", {"misses": 0, "fallback": 0})
        inc = Incremental(SGDClassifier(), chunk_size=100, random_state=0)
        inc.fit(sX, sy, classes=[0.0, 1.0])
        for _ in range(39):
            inc.partial_fit(sX, sy)
        after = diagnostics.program_report()["programs"]["sgd.pass"]
    assert after["misses"] - before["misses"] == 1
    assert after["fallback"] == before["fallback"]
    spans = _pass_roots()[-40:]
    assert [s.attrs["blocks"] for s in spans] == [8] * 40
    assert all(s.attrs["host_waits"] == 0 for s in spans[1:])
    assert inc.estimator_.t_ == 40 * 8


@pytest.mark.parametrize("case", ["sample_weight", "class_weight",
                                  "host_labels", "sklearn_model"])
def test_what_keeps_the_host_stream(case):
    """Per-row weights, labels on the host and models that are not this
    package's SGD keep the host stream, which reads the table back."""
    from sklearn.linear_model import SGDClassifier as SkSGD

    X, y = _table(rows=300)
    sX, sy = shard_rows(X), shard_rows(y)
    model, kwargs = SGDClassifier(), {"classes": [0.0, 1.0]}
    if case == "sample_weight":
        kwargs["sample_weight"] = np.ones(300, np.float32)
    elif case == "class_weight":
        model = SGDClassifier(class_weight={0.0: 2.0})
    elif case == "host_labels":
        sy = y
    else:
        model = SkSGD()
    assert not _sgd_pass.takes(model, sX, sy, kwargs)
    assert _sgd_pass.takes(SGDClassifier(), sX, shard_rows(y),
                           {"classes": [0.0, 1.0]})


def test_resident_labels_are_checked():
    X, y = _table(rows=400)
    sX = shard_rows(X)
    with pytest.raises(ValueError, match="not in `classes`"):
        _partial.fit(SGDClassifier(), sX, shard_rows(y + 2.0),
                     chunk_size=CHUNK, classes=[0.0, 1.0])
    with pytest.raises(ValueError, match="classes must be passed"):
        _partial.fit(SGDClassifier(), sX, shard_rows(y), chunk_size=CHUNK)
    with pytest.raises(ValueError, match="different lengths"):
        _partial.fit(SGDClassifier(), sX, shard_rows(y[:-3]),
                     chunk_size=CHUNK, classes=[0.0, 1.0])
