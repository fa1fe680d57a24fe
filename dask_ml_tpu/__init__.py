"""dask-ml-tpu: TPU-native scalable machine learning.

A ground-up re-design of the capabilities of the reference library
(stsievert/dask-ml) for TPU hardware.  Where the reference builds dask task
graphs over chunked arrays and hands them to the distributed scheduler, this
framework shards ``jax.Array`` rows over a ``jax.sharding.Mesh`` and compiles
each algorithm into a single XLA program per step (``jax.jit`` +
``shard_map``), with collectives (``psum`` / ``all_gather``) riding ICI
instead of TCP shuffles.

Two execution planes (mirroring the reference's two styles — see SURVEY.md §1):

* **Lazy graph style** (most estimators in the reference) → jitted SPMD steps
  over sharded arrays.
* **Dynamic futures style** (``model_selection._incremental`` et al.) → a
  host-side asyncio orchestrator multiplexing many small models over devices.

Reference parity citations use the convention
``dask_ml/<path>.py :: <symbol>`` (the reference mount was empty at build
time; see SURVEY.md header for provenance).
"""

__version__ = "0.1.0"

from .programs.cache import enable_persistent_cache as _enable_cache

# before anything can compile: eager ops and non-cached programs are kept too
_enable_cache()

from . import core  # noqa: F401,E402
from . import linalg  # noqa: F401
from . import metrics  # noqa: F401
from . import preprocessing  # noqa: F401
from . import decomposition  # noqa: F401
from . import cluster  # noqa: F401
from . import datasets  # noqa: F401
from . import solvers  # noqa: F401
from . import linear_model  # noqa: F401
from . import feature_extraction  # noqa: F401
from . import impute  # noqa: F401
from . import io  # noqa: F401
from . import data  # noqa: F401
from . import pipeline  # noqa: F401
from . import ops  # noqa: F401
from . import naive_bayes  # noqa: F401
from . import ensemble  # noqa: F401
from . import compose  # noqa: F401
from . import wrappers  # noqa: F401
from . import _partial  # noqa: F401
from . import checkpoint  # noqa: F401
from . import resilience  # noqa: F401
from . import serve  # noqa: F401
from . import sanitize  # noqa: F401
from . import obs  # noqa: F401
from . import control  # noqa: F401
from . import diagnostics  # noqa: F401
from . import model_selection  # noqa: F401

__all__ = [
    "core",
    "linalg",
    "metrics",
    "preprocessing",
    "decomposition",
    "cluster",
    "datasets",
    "solvers",
    "linear_model",
    "feature_extraction",
    "impute",
    "io",
    "data",
    "pipeline",
    "ops",
    "naive_bayes",
    "ensemble",
    "checkpoint",
    "resilience",
    "serve",
    "compose",
    "control",
    "diagnostics",
    "obs",
    "sanitize",
    "wrappers",
    "model_selection",
    "__version__",
]
