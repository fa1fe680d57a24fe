"""The one-read SGD step kernel (``linear_model/_sgd_kernel.py``), run
here in Pallas's TPU interpret mode, held to the XLA step it stands in
for (``_sgd.py :: sgd_step`` on the block's slice, as the resident pass
takes it) to float32 reassociation: every loss, one and three output
columns, an unaligned last block, a table that ends inside a tile with
NaN past its end, one and eight shards.  And what the kernel's path
costs a process that never takes it: nothing of Pallas is imported."""

import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dask_ml_tpu import diagnostics
from dask_ml_tpu.core import device_mesh, shard_rows, use_mesh
from dask_ml_tpu.core.mesh import MeshHolder
from dask_ml_tpu.linear_model import SGDClassifier, SGDRegressor
from dask_ml_tpu.linear_model import _sgd_kernel, _sgd_pass
from dask_ml_tpu.linear_model._sgd import ova_targets, sgd_step, sgd_update

CLASSIFIER = ["log_loss", "hinge", "squared_hinge", "modified_huber"]
REGRESSOR = ["squared_error", "huber"]
STEP = dict(penalty="l2", schedule="optimal", fit_intercept=True)


@pytest.fixture
def interpret():
    """Pallas's TPU interpret mode for the kernels traced inside, reading
    NaN wherever a tile overhangs its array."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode(pltpu.InterpretParams(
            out_of_bounds_reads="uninitialized",
            uninitialized_memory="nan")):
        yield


def _problem(loss, k, rows=3000, d=5, seed=0):
    """A table, labels, mask (the last rows padding), a state away from 0
    and the hyperparameters: everything a step takes."""
    rng = np.random.RandomState(seed)
    x = rng.normal(size=(rows, d)).astype(np.float32)
    if loss in REGRESSOR:
        y = (x @ rng.normal(size=d)).astype(np.float32)
        classes = None
    else:
        n = 2 if k == 1 else k
        y = rng.randint(0, n, size=rows).astype(np.float32)
        classes = np.arange(n, dtype=np.float32)
    mask = np.ones(rows, np.float32)
    mask[-5:] = 0.0
    model = SGDRegressor(loss=loss) if classes is None else SGDClassifier(
        loss=loss)
    hyper = {name: jnp.float32(v) for name, v in model._hyper().items()}
    state = {"coef": jnp.asarray(0.1 * rng.normal(size=(d, k)), jnp.float32),
             "intercept": jnp.asarray(0.1 * rng.normal(size=k), jnp.float32),
             "t": jnp.float32(7.0)}
    return x, y, mask, classes, state, hyper


def _xla_step(x, y, mask, classes, state, hyper, loss, start, block, size):
    """Today's step on the block ``[start, start + block)``: ``sgd_step``
    on the ``size`` rows of the slice the pass takes (clipped to the
    table, so the last block's starts earlier), the rows outside the
    block masked by index."""
    at = min(max(start, 0), x.shape[0] - size)
    rows = at + np.arange(size)
    own = (rows >= start) & (rows < start + block)
    yb = y[at:at + size]
    yb = (ova_targets(jnp.asarray(yb), jnp.asarray(classes))
          if classes is not None else jnp.asarray(yb)[:, None])
    new, _ = jax.jit(lambda st: sgd_step(
        st, jnp.asarray(x[at:at + size]), yb,
        jnp.where(jnp.asarray(own), jnp.asarray(mask[at:at + size]), 0.0),
        hyper, loss=loss, **STEP))(state)
    return new


def _kernel_step(x, y, mask, classes, state, hyper, loss, lo, hi, size):
    """The same step from the kernel's sums over rows ``[lo, hi)``."""
    positive = None
    if classes is not None:
        positive = jnp.asarray(classes[1:] if len(classes) == 2 else classes)

    @jax.jit
    def step(st, x, y, mask):
        gx, gs, n = _sgd_kernel.block_sums(
            x, y, mask, lo, hi, st["coef"], st["intercept"], positive,
            hyper["epsilon"], loss=loss, size=size)
        return sgd_update(st, gx / n, gs / n, hyper, **STEP)

    return step(state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask))


def _same(a, b):
    """Equal to float32 reassociation of the sums."""
    for key in ("coef", "intercept"):
        ref = np.asarray(b[key], np.float64)
        scale = max(float(np.abs(ref).max()), 1e-30)
        assert float(np.abs(np.asarray(a[key]) - ref).max()) <= 2e-6 * scale
    assert float(a["t"]) == float(b["t"])


# (start, block, size) in a 3,000-row table: a whole block from a tile's
# bound; the last block, whose slice starts at 1,500 (no bound) and whose
# rows start at 2,000; and a block whose rows end with the table, inside a
# tile of 2,048 rows that overhangs it
PLACEMENTS = {"aligned": (0, 1500, 1500), "last": (2000, 1500, 1500),
              "ragged": (1800, 1200, 1200)}
CASES = [(loss, k) for loss in CLASSIFIER for k in (1, 3)] + [
    (loss, 1) for loss in REGRESSOR]


@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
@pytest.mark.parametrize("loss,k", CASES)
def test_block_sums_make_the_step(interpret, loss, k, placement):
    x, y, mask, classes, state, hyper = _problem(loss, k)
    start, block, size = PLACEMENTS[placement]
    want = _xla_step(x, y, mask, classes, state, hyper, loss, start, block,
                     size)
    lo, hi = start, min(start + block, x.shape[0])
    # whatever lies outside the block is never multiplied: NaN there
    # (and past the table's end, where the interpreter reads NaN) leaves
    # the step as it was
    x, y, mask = x.copy(), y.copy(), mask.copy()
    for a in (x, y, mask):
        a[:lo] = np.nan
        a[hi:] = np.nan
    got = _kernel_step(x, y, mask, classes, state, hyper, loss, lo, hi,
                       size)
    _same(got, want)


def _passes(chips, kernel, loss, n_classes, rows, block, passes=2):
    """``passes`` resident passes over shuffled blocks, the state carried
    between them; returns the state and the pass program's misses."""
    rng = np.random.RandomState(1)
    x = rng.normal(size=(rows, 6)).astype(np.float32)
    if loss in REGRESSOR:
        y = (x @ rng.normal(size=6)).astype(np.float32)
        model, targets, classes = SGDRegressor(loss=loss), "value", [0.0, 1.0]
    else:
        y = rng.randint(0, n_classes, size=rows).astype(np.float32)
        model, targets = SGDClassifier(loss=loss), "ova"
        classes = list(np.arange(n_classes, dtype=np.float32))
        model._set_classes(np.asarray(classes))
    starts = list(range(0, rows, block))
    rng.shuffle(starts)
    with use_mesh(device_mesh(chips)):
        sx, sy = shard_rows(x), shard_rows(y)
        mesh = sx.data.sharding.mesh
        model._ensure_state(6)
        state, hyper, classes, starts = jax.device_put(
            (model._state, model._hyper(), np.asarray(classes, np.float32),
             np.asarray(starts, np.int32)), NamedSharding(mesh, P()))
        book = diagnostics.program_report()["programs"].get(
            "sgd.pass", {"misses": 0})["misses"]
        for _ in range(passes):
            state = _sgd_pass._pass_program(
                state, sx.data, sy.data, sx.mask, starts, hyper, classes,
                block=block, targets=targets, loss=loss, **STEP,
                mesh_holder=MeshHolder(mesh), axes=_sgd_pass._row_axes(sx),
                kernel=kernel)
        misses = diagnostics.program_report()["programs"]["sgd.pass"][
            "misses"] - book
    return jax.device_get(state), misses


@pytest.mark.parametrize("chips,loss,n_classes,rows", [
    (1, "log_loss", 2, 4100), (1, "modified_huber", 3, 4100),
    (1, "huber", 0, 4100), (8, "log_loss", 2, 8 * 1100)])
def test_kernel_pass_is_the_xla_pass(interpret, chips, loss, n_classes,
                                     rows):
    """Two passes over shuffled blocks of 1,500 rows, a short last one,
    blocks that straddle the shards on eight: the kernel's pass is the
    XLA step's, one compile for both passes."""
    want, _ = _passes(chips, False, loss, n_classes, rows, 1500)
    got, misses = _passes(chips, True, loss, n_classes, rows, 1500)
    assert misses == 1
    assert float(got["t"]) == float(want["t"]) == 2 * -(-rows // 1500)
    _same(got, want)


@pytest.mark.parametrize("features,rows,outputs,covered", [
    (28, 62_500_000, 1, True), (28, 1023, 1, False), (28, 4096, 4, True),
    (28, 4096, 5, False), (1024, 4096, 1, True), (1025, 4096, 1, False)])
def test_what_the_kernel_covers(features, rows, outputs, covered):
    """Shapes alone decide, and the tile is a function of them: 32,768
    rows for the cell's 28 features, fewer where the features or the
    block or the table ask for it, always whole loads."""
    assert _sgd_kernel.covers(features, rows, outputs) is covered
    if covered:
        tile = _sgd_kernel.tile_rows(features, rows, 1_048_576)
        assert tile % _sgd_kernel.LOAD == 0 and tile <= rows
        assert -(-features // 8) * 8 * 4 * tile <= _sgd_kernel.TILE_BYTES
    assert _sgd_kernel.tile_rows(28, 62_500_000, 1_048_576) == 32_768
    assert _sgd_kernel.tile_rows(28, 62_500_000, 1500) == 2048


def test_a_process_that_keeps_xla_imports_no_pallas():
    """``import dask_ml_tpu`` and a resident pass that keeps XLA's step
    (this CPU) import nothing of Pallas; building the kernel imports
    Pallas without its GPU interpreter."""
    code = """
import sys
import numpy as np
import dask_ml_tpu
from dask_ml_tpu.core import shard_rows
from dask_ml_tpu.linear_model import SGDClassifier, _sgd_kernel
from dask_ml_tpu.wrappers import Incremental

def pallas():
    return sorted(m for m in sys.modules if "pallas" in m)

assert pallas() == [], pallas()
x = np.random.RandomState(0).normal(size=(2048, 4)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.float32)
inc = Incremental(SGDClassifier(), chunk_size=512, random_state=0)
inc.fit(shard_rows(x), shard_rows(y), classes=[0.0, 1.0])
assert inc.estimator_.t_ == 4 and pallas() == [], pallas()
pl, pltpu = _sgd_kernel._pallas()
assert pl.pallas_call and pltpu.PrefetchScalarGridSpec
assert "jax.experimental.mosaic.gpu" not in sys.modules
assert _sgd_kernel._GPU_INTERPRETER not in sys.modules
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split()[-1] == "ok"
