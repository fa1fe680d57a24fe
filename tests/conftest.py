"""Test harness: an 8-device virtual CPU mesh.

This is the direct analogue of the reference's ``distributed.utils_test.
gen_cluster`` (in-process scheduler + workers — SURVEY.md §4.3): the same
SPMD code paths that run on a TPU pod run here on 8 virtual CPU devices, so
multi-device sharding and collectives are exercised on every test run.

Must set XLA flags BEFORE jax initializes — hence the top of conftest.
"""

import os
import re

# DASK_ML_TPU_TEST_DEVICES sweeps the virtual mesh size (default 8):
# odd counts (5, 7) are the adversarial cases for pad+mask divisibility.
_N_DEV = int(os.environ.get("DASK_ML_TPU_TEST_DEVICES", "8"))

os.environ["JAX_PLATFORMS"] = "cpu"
# REWRITE any pre-existing count rather than skip: a stale flag from the
# caller's shell would silently override the sweep knob (subprocesses the
# tests spawn inherit this one)
os.environ["XLA_FLAGS"] = (
    re.sub(r"--xla_force_host_platform_device_count=\d+", "",
           os.environ.get("XLA_FLAGS", "")).strip()
    + f" --xla_force_host_platform_device_count={_N_DEV}"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", _N_DEV)

import faulthandler  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# -- crash / hang forensics ----------------------------------------------
# The round-5 suite died once with a bare "Fatal Python error" and no
# traceback.  faulthandler is armed explicitly (pytest's builtin plugin
# usually does this too, but an explicit enable survives
# `-p no:faulthandler` runs and pre-collection crashes), and every test
# arms a watchdog that dumps ALL thread stacks when the test exceeds
# DASK_ML_TPU_TEST_TIMEOUT_S (default 300 s; 0 disables).  The dump is
# NON-fatal: the driver's outer `timeout -k` still bounds the suite, but
# a hang/crash now leaves stacks on stderr instead of a silent abort.
faulthandler.enable()

_TEST_TIMEOUT_S = float(os.environ.get("DASK_ML_TPU_TEST_TIMEOUT_S", "300"))

# grafttrace armed for the whole suite: span rings + flight recorder
# cost is within the tier-1 noise floor (three records a block on the
# streamed path, tests/test_obs.py), and it buys the watchdog dump
# below the "which block/round was in flight" context — faulthandler
# alone shows frames, not fit structure.
from dask_ml_tpu import obs as _obs  # noqa: E402

_obs.enable()


def _watchdog_dump(nodeid: str) -> None:
    """Flight-recorder half of the hang dump (runs on a plain timer
    thread: faulthandler's C-level dumper cannot run Python, so the
    span-path/flight context needs its own timer)."""
    _obs.flight_dump(
        reason=f"test watchdog: {nodeid} exceeded {_TEST_TIMEOUT_S:g}s",
        n=32,
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item):
    import threading as _threading

    timer = None
    if _TEST_TIMEOUT_S > 0:
        faulthandler.dump_traceback_later(_TEST_TIMEOUT_S, exit=False)
        timer = _threading.Timer(
            _TEST_TIMEOUT_S, _watchdog_dump, args=(item.nodeid,)
        )
        timer.daemon = True
        timer.start()
    try:
        yield
    finally:
        if _TEST_TIMEOUT_S > 0:
            faulthandler.cancel_dump_traceback_later()
        if timer is not None:
            timer.cancel()


def tracing_guard():
    """Fail the test that leaks tracing state, not the ones after it
    (the generator body of the autouse fixture below; tests/test_obs.py
    drives it on planted leaks).  A test leaves as it found them whether
    span recording is armed and which span is open on its thread; a
    leak is put right for the next test, then named."""
    armed, open_id = _obs.enabled(), _obs.current_span_id()
    yield
    leaks = []
    if armed and not _obs.enabled():
        _obs.enable()
        leaks.append("left span recording disarmed (an obs.disable() "
                     "with no obs.enable() after it): every later test "
                     "of this process would have recorded nothing")
    if open_id is None and _obs.current_span_id() is not None:
        leaks.append(f"left a span open on its thread "
                     f"({_obs.open_span_paths()}): every later root "
                     f"span of this process would have nested under it")
        for open_span in reversed(list(_obs.spans._stack())):
            open_span.__exit__(None, None, None)
    if leaks:
        pytest.fail("; ".join(leaks))


@pytest.fixture(autouse=True)
def _tracing_left_as_found():
    yield from tracing_guard()


@pytest.fixture(autouse=True)
def _default_mesh_left_as_found():
    """``benchmarks/run.py :: run_cell`` sets the process-default mesh to
    its cell's chips and leaves it so (a benchmark's process runs one
    cell and ends).  A test that drives it (``tests/test_pca_tsqr.py``
    on one device) would hand every later test of its worker that mesh:
    fits on one shard where eight were meant, and arrays of two meshes
    in one program.  Put back, whatever the test did."""
    from dask_ml_tpu.core import mesh as _mesh

    before = getattr(_mesh._state, "default_mesh", None)
    yield
    _mesh.set_mesh(before)


class PeakInside:
    """``with gauge:`` around a planted sleep; ``gauge.peak`` is the most
    threads inside at once: that work overlapped, read without a clock."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self._inside = self.peak = 0

    def __enter__(self):
        with self._lock:
            self._inside += 1
            self.peak = max(self.peak, self._inside)

    def __exit__(self, *exc):
        with self._lock:
            self._inside -= 1


@pytest.fixture(scope="session")
def n_devices():
    """The harness-configured virtual device count."""
    return _N_DEV


def require_devices_divisible(k: int) -> int:
    """Skip the calling test unless the device count divides by ``k``
    (mesh-shape-sensitive tests under the DASK_ML_TPU_TEST_DEVICES
    sweep); returns the device count."""
    n = len(jax.devices())
    if n % k:
        pytest.skip(f"needs a device count divisible by {k} (have {n})")
    return n


@pytest.fixture(scope="session")
def mesh():
    from dask_ml_tpu.core import get_mesh

    return get_mesh()


def retry_flaky(attempts=2, match=None):
    """Auto-retry decorator for LOAD-flaky tests (not logic-flaky ones).

    Re-runs the test up to ``attempts`` times, but ONLY when the failure
    text matches ``match`` (a regex) — a real assertion failure must
    surface on the first run, not burn retries.  Use sparingly: the only
    legitimate customer is resource-starvation noise like
    ``test_three_process_group``'s coordination-service heartbeat
    timeouts when 3 jax processes starve the 2-core box (ROADMAP env
    note); that class passes in isolation and wastes a tier-1 lane when
    it loses the scheduling lottery.
    """
    import functools
    import re as _re

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            last = None
            for attempt in range(attempts):
                try:
                    return fn(*args, **kwargs)
                except Exception as e:  # noqa: BLE001 - filtered below
                    text = f"{type(e).__name__}: {e}"
                    if match is not None and not _re.search(
                            match, text, _re.IGNORECASE | _re.DOTALL):
                        raise
                    last = e
                    if attempt + 1 < attempts:
                        import warnings

                        warnings.warn(
                            f"retry_flaky: {fn.__name__} attempt "
                            f"{attempt + 1}/{attempts} hit a matched "
                            f"flake, retrying: {text[:200]}",
                            stacklevel=2,
                        )
            raise last

        return wrapper

    return deco


@pytest.fixture
def sanitizer():
    """A scoped graftsan runtime sanitizer (dask_ml_tpu/sanitize/):
    compile/transfer/dispatch detectors armed for exactly this test.
    Fail-fast: an off-thread dispatch or compile raises at the violating
    call; use ``with sanitizer.steady():`` around the post-warmup phase
    to arm the implicit-transfer guard and make new compiles
    violations."""
    from dask_ml_tpu import sanitize

    with sanitize.sanitize(label="pytest") as s:
        yield s


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def xy_classification(rng):
    """Small dense classification problem (reference conftest pattern)."""
    n, d = 100, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w + 0.1 * rng.normal(size=n) > 0).astype(np.int32)
    return X, y


@pytest.fixture
def xy_regression(rng):
    n, d = 120, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w + 0.05 * rng.normal(size=n)).astype(np.float32)
    return X, y


# -- Hypothesis profiles -------------------------------------------------
# Default = derandomized: the suite must be deterministic-green for CI /
# the driver (r3 verdict: random draws made the suite flaky at head —
# property tests are a DISCOVERY tool, and discovery belongs in the
# explicit 'explore' profile, not in every CI run).
#   HYPOTHESIS_PROFILE=explore python -m pytest tests/test_properties.py
# runs the randomized search that has found real bugs each round.
try:
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("ci", derandomize=True)
    _hyp_settings.register_profile("explore", derandomize=False)
    _hyp_settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:  # pragma: no cover
    pass
