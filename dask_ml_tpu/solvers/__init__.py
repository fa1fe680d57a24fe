"""Solver library — twin of the external ``dask_glm`` package (SURVEY.md §2
#20): iterative convex solvers over row-sharded arrays.

Where dask_glm drives scipy optimizers from the host with one
scatter/gather round per iteration, every solver here is a device-native
XLA program: gradients come from ``jax.value_and_grad`` over the masked
loss (the cross-shard reduction is a psum inserted by XLA), line searches
are ``lax.while_loop``s, and ADMM's per-chunk local L-BFGS runs inside
``shard_map`` with a single psum per consensus round.
"""

from .families import Logistic, Normal, Poisson, multinomial  # noqa: F401
from .regularizers import L1, L2, ElasticNet, get_regularizer  # noqa: F401
from .algorithms import (  # noqa: F401
    DISPATCH_COUNTS,
    admm,
    gradient_descent,
    lbfgs,
    newton,
    lambda_sweep,
    packed_solve,
    proximal_grad,
    reset_dispatch_counts,
)
from .lbfgs_core import lbfgs_minimize  # noqa: F401

__all__ = [
    "Logistic",
    "Normal",
    "Poisson",
    "multinomial",
    "L1",
    "L2",
    "ElasticNet",
    "get_regularizer",
    "admm",
    "gradient_descent",
    "lbfgs",
    "newton",
    "proximal_grad",
    "lambda_sweep",
    "packed_solve",
    "DISPATCH_COUNTS",
    "reset_dispatch_counts",
    "lbfgs_minimize",
]
