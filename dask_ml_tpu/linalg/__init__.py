"""Distributed linear algebra (replaces the reference's reliance on external
``da.linalg.svd`` (TSQR) and ``da.linalg.svd_compressed`` (Halko randomized
SVD) — SURVEY.md §2 L2, §3.4)."""

from .tsqr import tsqr, tsqr_r, tsqr_svd  # noqa: F401
from .randomized import randomized_svd  # noqa: F401

__all__ = ["tsqr", "tsqr_r", "tsqr_svd", "randomized_svd"]
