import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dask_ml_tpu.core import (
    DATA_AXIS,
    ShardedRows,
    data_axis_size,
    device_mesh,
    get_mesh,
    shard_rows,
    unshard,
    use_mesh,
)
from dask_ml_tpu.core.sharded import masked_mean, masked_sum, masked_var
from dask_ml_tpu.utils import handle_zeros_in_scale, svd_flip


def test_harness_device_count_applied(n_devices):
    assert len(jax.devices()) == n_devices


def test_default_mesh_covers_devices():
    mesh = get_mesh()
    assert (data_axis_size(mesh) * mesh.shape["model"]
            == len(jax.devices()))


def test_use_mesh_scoping():
    small = device_mesh(4)
    with use_mesh(small):
        assert get_mesh() is small
    assert get_mesh() is not small


@pytest.mark.parametrize("n", [16, 17, 23, 8])
def test_shard_rows_pads_and_masks(n):
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    s = shard_rows(x)
    assert s.n_samples == n
    assert s.padded % data_axis_size() == 0
    assert float(jnp.sum(s.mask)) == n
    np.testing.assert_array_equal(unshard(s), x)


def test_sharding_is_row_partitioned():
    x = np.ones((16, 4), dtype=np.float32)
    s = shard_rows(x)
    assert s.data.sharding.spec[0] == DATA_AXIS


def test_masked_reductions_match_numpy():
    rng = np.random.RandomState(0)
    x = rng.normal(size=(37, 5)).astype(np.float32)
    s = shard_rows(x)
    np.testing.assert_allclose(
        np.asarray(masked_sum(s.data, s.mask)), x.sum(0), rtol=1e-5
    )
    # atol floor: the anchor-shifted mean rounds differently from
    # np.mean by ~1 ulp of the spread, which for a near-zero column
    # mean exceeds any pure-rtol bound
    np.testing.assert_allclose(
        np.asarray(masked_mean(s.data, s.mask)), x.mean(0), rtol=1e-5,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(masked_var(s.data, s.mask)), x.var(0), rtol=1e-4
    )


def test_masked_reduction_compiles_once_under_jit():
    x = np.ones((24, 2), dtype=np.float32)
    s = shard_rows(x)
    out = jax.jit(masked_sum)(s.data, s.mask)
    np.testing.assert_allclose(np.asarray(out), [24.0, 24.0])


def test_handle_zeros_in_scale():
    scale = jnp.array([1.0, 0.0, 2.0])
    out = np.asarray(handle_zeros_in_scale(scale))
    np.testing.assert_array_equal(out, [1.0, 1.0, 2.0])


def test_svd_flip_deterministic_signs():
    rng = np.random.RandomState(1)
    a = rng.normal(size=(20, 4))
    u, s, vt = np.linalg.svd(a, full_matrices=False)
    u1, v1 = svd_flip(jnp.asarray(u), jnp.asarray(vt))
    u2, v2 = svd_flip(jnp.asarray(-u), jnp.asarray(-vt))
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(u1) * s @ np.asarray(v1), a, atol=1e-5
    )


def test_sharded_rows_is_frozen():
    s = shard_rows(np.ones((8, 2), dtype=np.float32))
    assert isinstance(s, ShardedRows)
    with pytest.raises(Exception):
        s.n_samples = 5


class TestChunkHelpers:
    """Reference: ``dask_ml/utils.py :: check_chunks / check_matching_blocks /
    slice_columns`` — the chunk-spec trio, re-done for the row-shard layout."""

    def test_check_chunks_auto(self):
        from dask_ml_tpu.utils import check_chunks

        assert check_chunks(160) == 10  # <=16 blocks
        assert check_chunks(5) == 1

    def test_check_chunks_int_and_tuple(self):
        from dask_ml_tpu.utils import check_chunks

        assert check_chunks(100, 4, 25) == 25
        assert check_chunks(100, 4, (25, 4)) == 25
        with pytest.raises(ValueError, match="column chunking"):
            check_chunks(100, 4, (25, 2))
        with pytest.raises(ValueError, match="positive"):
            check_chunks(100, 4, 0)

    def test_check_matching_blocks(self):
        from dask_ml_tpu.utils import check_matching_blocks

        a = shard_rows(np.ones((20, 2), dtype=np.float32))
        b = shard_rows(np.ones((20, 3), dtype=np.float32))
        check_matching_blocks(a, b)  # same layout: fine
        c = shard_rows(np.ones((21, 2), dtype=np.float32))
        with pytest.raises(ValueError, match="[Ii]nconsistent"):
            check_matching_blocks(a, c)

    def test_slice_columns_array_and_sharded(self):
        import pandas as pd

        from dask_ml_tpu.utils import slice_columns

        x = np.arange(24, dtype=np.float32).reshape(6, 4)
        np.testing.assert_array_equal(
            slice_columns(x, [1, 3]), x[:, [1, 3]]
        )
        assert slice_columns(x, None) is x
        s = shard_rows(x)
        out = slice_columns(s, [0, 2])
        assert isinstance(out, ShardedRows) and out.n_samples == 6
        np.testing.assert_array_equal(unshard(out), x[:, [0, 2]])
        df = pd.DataFrame(x, columns=list("abcd"))
        assert list(slice_columns(df, ["b", "d"]).columns) == ["b", "d"]

    def test_slice_columns_boolean_mask(self):
        from dask_ml_tpu.utils import slice_columns

        x = np.arange(24, dtype=np.float32).reshape(6, 4)
        mask = np.array([True, False, True, False])
        np.testing.assert_array_equal(
            unshard(slice_columns(shard_rows(x), mask)), x[:, mask]
        )

    def test_partial_fit_accepts_tuple_chunks(self):
        from sklearn.linear_model import SGDClassifier as SkSGD

        from dask_ml_tpu import _partial

        rng = np.random.RandomState(0)
        x = rng.rand(60, 4).astype(np.float32)
        y = (rng.rand(60) > 0.5).astype(np.int32)
        m = _partial.fit(
            SkSGD(random_state=0), x, y, chunk_size=(20, 4),
            classes=[0, 1],
        )
        assert hasattr(m, "coef_")
        with pytest.raises(ValueError, match="column chunking"):
            _partial.fit(SkSGD(), x, y, chunk_size=(20, 2), classes=[0, 1])
