"""Sparse text at scale: out-of-core CountVectorizer -> streamed SVD.

The corpus is consumed lazily (bounded-window chunks, never
materialized); TruncatedSVD.fit_streamed densifies one block at a time,
so a 100k-vocabulary pipeline fits in O(features x sketch) memory.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402

from dask_ml_tpu.decomposition import TruncatedSVD  # noqa: E402
from dask_ml_tpu.feature_extraction.text import CountVectorizer  # noqa: E402

corpus = [
    f"topic{i % 7} shares words with topic{(i + 1) % 7} but not {i % 97}"
    for i in range(5000)
]
vec = CountVectorizer().fit(corpus)  # global document frequencies
svd = TruncatedSVD(n_components=5, random_state=0)
svd.fit_streamed(lambda: vec.stream_transform(corpus))
print(f"vocabulary: {len(vec.vocabulary_)} terms")
print("explained variance ratio:",
      np.asarray(svd.explained_variance_ratio_).round(4))
