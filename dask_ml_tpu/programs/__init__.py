"""Central compiled-program cache, shape bucketing, and compile-ahead.

The ROADMAP ``[compile]`` lane (design.md §12): recompilation is the
hidden tax on every other lane — ragged streamed tails, heterogeneous
search configs, and variable serving shapes all retrigger XLA compiles.
This package is the one place program shapes are decided and compiled
programs live:

* :mod:`.bucket` — the ``DASK_ML_TPU_BUCKET`` shape-bucketing policy
  (off / pow2 / explicit ladders) behind the shared
  :func:`pad_block` every staged estimator path uses;
* :mod:`.cache` — :class:`CachedProgram`, the cache every step-program
  dispatch goes through instead of a bare ``jax.jit`` (the
  ``jit-outside-cache`` lint rule holds new code to that), with
  hit/miss/ahead-hit books, and the persistent compilation cache
  armed at package import (``JAX_COMPILATION_CACHE_DIR``, else
  ``<checkout>/.jax_cache``);
* :mod:`.ahead` — the blessed ``dask-ml-tpu-compile-ahead`` worker
  thread that pre-compiles the next bucket's program while the current
  block computes (``DASK_ML_TPU_COMPILE_AHEAD``).

``diagnostics.program_report()`` is the user-facing view of
:func:`report`.
"""

from .ahead import (  # noqa: F401
    AHEAD_ENV,
    AHEAD_THREAD_NAME,
    drain as drain_ahead,
    enabled as compile_ahead_enabled,
    submit,
    worker_alive as ahead_worker_alive,
)
from .bucket import (  # noqa: F401
    BUCKET_ENV,
    DEFAULT_BUCKETS,
    BucketPolicy,
    bucket_rows,
    pad_block,
    resolve_policy,
)
from .cache import (  # noqa: F401
    DEFAULT_CACHE_DIR,
    CachedProgram,
    cached_program,
    enable_persistent_cache,
    report,
    reset_counters,
)

__all__ = [
    "AHEAD_ENV",
    "AHEAD_THREAD_NAME",
    "ahead_worker_alive",
    "BUCKET_ENV",
    "DEFAULT_BUCKETS",
    "DEFAULT_CACHE_DIR",
    "BucketPolicy",
    "CachedProgram",
    "bucket_rows",
    "cached_program",
    "compile_ahead_enabled",
    "drain_ahead",
    "enable_persistent_cache",
    "pad_block",
    "report",
    "reset_counters",
    "resolve_policy",
    "submit",
]
