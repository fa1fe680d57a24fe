"""Categorical encoders (reference: ``dask_ml/preprocessing/_encoders.py`` ::
``OneHotEncoder`` and ``dask_ml/preprocessing/data.py`` :: ``OrdinalEncoder``).

The reference leans on pandas categorical dtypes propagated through dask
dataframe partitions.  Category *inventories* are inherently small (they fit
on the host by definition), so fit and the per-row inventory lookup happen
host-side (string/object columns are not device types anyway); the wide part
— expanding integer codes into one-hot columns — runs on device via
``jax.nn.one_hot``, and dense one-hot output feeds the MXU directly (sparse
output is TPU-hostile; see SURVEY.md §7 hard-part (e)).  Sharded input
yields sharded output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from ..base import TPUEstimator, TransformerMixin
from ..core.sharded import ShardedRows, unshard


def _is_frame(X) -> bool:
    return isinstance(X, pd.DataFrame)


def _host_2d(X) -> np.ndarray:
    x = unshard(X) if isinstance(X, ShardedRows) else np.asarray(X)
    if x.ndim != 2:
        raise ValueError(f"Expected 2D input, got shape {x.shape}")
    return x


def _column_categories(col: np.ndarray) -> np.ndarray:
    """Sorted unique non-missing values of one column (host-side —
    inventories are small).  Missing values (None/NaN) are not categories,
    matching the reference's pandas-categorical semantics."""
    col = np.asarray(col)
    if col.dtype.kind in "OUS":
        vals = pd.unique(col.astype(object).ravel())
        vals = vals[~pd.isna(vals)]
        return np.sort(vals)
    if col.dtype.kind == "f":
        return np.unique(col[~np.isnan(col)])
    return np.unique(col)


def _encode_column(cats: np.ndarray, values: np.ndarray):
    """(codes, known): indices of ``values`` into ``cats`` preserving the
    given category order (user-supplied inventories need not be sorted).
    Missing and unknown values encode as -1, like pandas categoricals
    (``pd.Categorical(values, categories=cats)`` itself is deprecated for
    values outside the categories since pandas 3)."""
    codes = pd.Index(np.asarray(cats)).get_indexer(np.asarray(values))
    return codes, codes >= 0


class OneHotEncoder(TransformerMixin, TPUEstimator):
    """Encode categorical features as a dense one-hot matrix.

    Differences from the reference, by design:

    * ``sparse_output`` defaults to **False** — dense bfloat16/float32 one-hot
      blocks are what the MXU consumes; scipy sparse output is produced
      host-side only if explicitly requested.
    * For array input the inventory lookup runs host-side and the one-hot
      expansion on device (``jax.nn.one_hot``); sharded in → sharded out.

    DataFrame input uses pandas categoricals like the reference and returns a
    DataFrame of dummy columns.
    """

    def __init__(self, categories="auto", drop=None, sparse_output=False,
                 dtype=np.float32, handle_unknown="error"):
        self.categories = categories
        self.drop = drop
        self.sparse_output = sparse_output
        self.dtype = dtype
        self.handle_unknown = handle_unknown

    def _compute_drop_idx(self):
        """sklearn semantics: None | 'first' | 'if_binary' | per-feature
        category array.  Sets ``drop_idx_`` (object array of int-or-None
        per feature, or None)."""
        if self.drop is None:
            self.drop_idx_ = None
            return
        cats = self.categories_
        if isinstance(self.drop, str):
            if self.drop == "first":
                self.drop_idx_ = np.array([0] * len(cats), dtype=object)
            elif self.drop == "if_binary":
                self.drop_idx_ = np.array(
                    [0 if len(c) == 2 else None for c in cats], dtype=object
                )
            else:
                raise ValueError(
                    f"drop must be None, 'first', 'if_binary' or an array; "
                    f"got {self.drop!r}"
                )
            return
        drop = np.asarray(self.drop, dtype=object)
        if drop.shape[0] != len(cats):
            raise ValueError(
                f"drop has {drop.shape[0]} entries for {len(cats)} features"
            )
        idxs = []
        for j, (c, val) in enumerate(zip(cats, drop)):
            where = np.flatnonzero(np.asarray(c, dtype=object) == val)
            if where.size == 0:
                raise ValueError(
                    f"drop value {val!r} is not a category of feature {j}"
                )
            idxs.append(int(where[0]))
        self.drop_idx_ = np.array(idxs, dtype=object)

    def _kept(self, j):
        """Column indices of feature j's one-hot block that survive drop."""
        n = len(self.categories_[j])
        if self.drop_idx_ is None or self.drop_idx_[j] is None:
            return list(range(n))
        return [i for i in range(n) if i != self.drop_idx_[j]]

    def fit(self, X, y=None):
        if self.handle_unknown not in ("error", "ignore"):
            raise ValueError(
                f"handle_unknown must be 'error' or 'ignore', got {self.handle_unknown!r}"
            )
        if _is_frame(X):
            self.feature_names_in_ = np.asarray(X.columns, dtype=object)
            if self.categories == "auto":
                self.categories_ = [
                    np.asarray(X[c].array.categories
                               if isinstance(X[c].dtype, pd.CategoricalDtype)
                               else _column_categories(X[c].to_numpy()))
                    for c in X.columns
                ]
            else:
                self.categories_ = [np.asarray(c) for c in self.categories]
            self.n_features_in_ = len(X.columns)
            self._frame_input_ = True
            self._compute_drop_idx()
            return self
        x = _host_2d(X)
        if self.categories == "auto":
            self.categories_ = [_column_categories(x[:, j]) for j in range(x.shape[1])]
        else:
            self.categories_ = [np.asarray(c) for c in self.categories]
        self.n_features_in_ = x.shape[1]
        self._frame_input_ = False
        self._compute_drop_idx()
        return self

    def _transform_frame(self, X: pd.DataFrame):
        if not getattr(self, "_frame_input_", False):
            raise ValueError(
                "This encoder was fitted on an array; pass an array to transform"
            )
        expected = list(self.feature_names_in_)
        if list(X.columns) != expected:
            raise ValueError(
                f"Column mismatch: fitted on {expected}, got {list(X.columns)}"
            )
        out = {}
        for j, c in enumerate(X.columns):
            cats = self.categories_[j]
            codes = pd.Categorical(X[c], categories=cats).codes
            if self.handle_unknown == "error" and (codes < 0).any():
                bad = set(X[c][codes < 0])
                raise ValueError(f"Found unknown categories {bad} in column {c}")
            for k in self._kept(j):
                out[f"{c}_{cats[k]}"] = (codes == k).astype(self.dtype)
        return pd.DataFrame(out, index=X.index)

    def transform(self, X):
        if _is_frame(X):
            return self._transform_frame(X)
        x = _host_2d(X)
        n, d = x.shape
        if d != self.n_features_in_:
            raise ValueError(f"X has {d} features; expected {self.n_features_in_}")
        code_cols = []
        for j in range(d):
            # Inventory lookup is host-side (inventories are small); only the
            # narrow integer codes cross to device — the wide one-hot
            # expansion happens there (jax.nn.one_hot → fused scatter).
            codes, known = _encode_column(self.categories_[j], x[:, j])
            if self.handle_unknown == "error" and not known.all():
                bad = set(np.asarray(x[:, j])[~known].tolist())
                raise ValueError(f"Found unknown categories {bad} in column {j}")
            code_cols.append(codes)
        codes_np = np.stack(code_cols, axis=1)
        sizes = [len(c) for c in self.categories_]

        def expand(codes_dev, j):
            oh = jax.nn.one_hot(codes_dev[:, j], sizes[j], dtype=self.dtype)
            kept = self._kept(j)
            if len(kept) != sizes[j]:
                oh = jnp.take(oh, jnp.asarray(kept), axis=1)
            return oh

        if isinstance(X, ShardedRows):
            from ..core.sharded import shard_rows

            s = shard_rows(codes_np)
            data = jnp.concatenate([expand(s.data, j) for j in range(d)], axis=1)
            return ShardedRows(data=data, mask=s.mask, n_samples=s.n_samples)
        codes_dev = jnp.asarray(codes_np)
        out = jnp.concatenate([expand(codes_dev, j) for j in range(d)], axis=1)
        if self.sparse_output:
            import scipy.sparse

            return scipy.sparse.csr_matrix(np.asarray(out))
        return out

    def get_feature_names_out(self, input_features=None):
        names = (self.feature_names_in_ if getattr(self, "_frame_input_", False)
                 else (input_features if input_features is not None
                       else [f"x{j}" for j in range(self.n_features_in_)]))
        out = []
        for j, (c, cats) in enumerate(zip(names, self.categories_)):
            for k in self._kept(j):
                out.append(f"{c}_{cats[k]}")
        return np.asarray(out, dtype=object)

    def inverse_transform(self, X):
        x = np.asarray(unshard(X) if isinstance(X, ShardedRows) else X)
        cols, start = [], 0
        for j, cats in enumerate(self.categories_):
            kept = self._kept(j)
            block = x[:, start:start + len(kept)]
            cats = np.asarray(cats)
            if len(kept) == len(cats):
                cols.append(cats[block.argmax(axis=1)])
            else:
                # all-zeros row means the dropped category
                hit = block.argmax(axis=1)
                picked = cats[np.asarray(kept)][hit]
                dropped = cats[int(self.drop_idx_[j])]
                cols.append(np.where(block.sum(axis=1) > 0, picked, dropped))
            start += len(kept)
        return np.stack(cols, axis=1)


class OrdinalEncoder(TransformerMixin, TPUEstimator):
    """Encode categorical columns as integer codes.

    DataFrame path mirrors the reference (`data.py :: OrdinalEncoder`):
    categorical columns become their pandas codes, other columns pass
    through, and fitted attributes record the dtypes for
    ``inverse_transform``.  Array path is the sklearn-style per-column
    searchsorted encode, run on device for numeric data.
    """

    def __init__(self, columns=None):
        self.columns = columns

    def fit(self, X, y=None):
        if _is_frame(X):
            columns = X.columns if self.columns is None else pd.Index(self.columns)
            self.columns_ = columns
            cat_cols = [c for c in columns
                        if isinstance(X[c].dtype, pd.CategoricalDtype)
                        or X[c].dtype == object
                        or pd.api.types.is_string_dtype(X[c].dtype)]
            self.categorical_columns_ = pd.Index(cat_cols)
            self.non_categorical_columns_ = columns.difference(self.categorical_columns_)
            self.dtypes_ = {
                c: (X[c].dtype if isinstance(X[c].dtype, pd.CategoricalDtype)
                    else pd.CategoricalDtype(np.unique(X[c].to_numpy())))
                for c in cat_cols
            }
            self._frame_input_ = True
            return self
        x = _host_2d(X)
        self.categories_ = [_column_categories(x[:, j]) for j in range(x.shape[1])]
        self.n_features_in_ = x.shape[1]
        self._frame_input_ = False
        return self

    def transform(self, X):
        if _is_frame(X):
            X = X.copy()
            for c in self.categorical_columns_:
                X[c] = pd.Categorical(X[c], dtype=self.dtypes_[c]).codes
            return X
        x = _host_2d(X)
        if x.shape[1] != self.n_features_in_:
            raise ValueError(
                f"X has {x.shape[1]} features; expected {self.n_features_in_}"
            )
        cols = []
        for j in range(x.shape[1]):
            codes, known = _encode_column(self.categories_[j], x[:, j])
            if not known.all():
                bad = set(np.asarray(x[:, j])[~known].tolist())
                raise ValueError(f"Found unknown categories {bad} in column {j}")
            cols.append(codes)
        codes_np = np.stack(cols, axis=1)
        if isinstance(X, ShardedRows):
            from ..core.sharded import shard_rows

            return shard_rows(codes_np)
        return jnp.asarray(codes_np)

    def inverse_transform(self, X):
        if getattr(self, "_frame_input_", False):
            X = X.copy()
            for c in self.categorical_columns_:
                dtype = self.dtypes_[c]
                X[c] = pd.Categorical.from_codes(np.asarray(X[c]), dtype=dtype)
            return X
        codes = np.asarray(unshard(X) if isinstance(X, ShardedRows) else X)
        cols = [np.asarray(self.categories_[j])[codes[:, j]] for j in range(codes.shape[1])]
        return np.stack(cols, axis=1)

    def get_feature_names_out(self, input_features=None):
        """One-to-one transform: output names are the input names
        (sklearn ``OrdinalEncoder`` contract; frame fits use the fitted
        columns).  ``input_features``, when given, is VALIDATED against
        the fitted surface — a frame fit requires the fitted column names
        verbatim, an array fit the fitted feature count — matching
        sklearn's ``_check_feature_names_in`` instead of silently
        echoing a mismatched list back."""
        if getattr(self, "_frame_input_", False):
            cols = list(self.columns_)
            if input_features is not None and list(input_features) != cols:
                raise ValueError(
                    f"input_features {list(input_features)!r} do not match "
                    f"the columns seen at fit {cols!r}"
                )
            return np.asarray(cols, dtype=object)
        if input_features is not None:
            if len(input_features) != self.n_features_in_:
                raise ValueError(
                    f"input_features has {len(input_features)} names; the "
                    f"encoder was fit on {self.n_features_in_} features"
                )
            return np.asarray(list(input_features), dtype=object)
        return np.asarray(
            [f"x{j}" for j in range(self.n_features_in_)], dtype=object
        )
