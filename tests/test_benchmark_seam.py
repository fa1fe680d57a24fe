"""The seam the benchmark reads the program through (tier-1's guard).

``benchmarks/layer_metrics/*`` find the program by strings: span names
(``glm.fit`` > ``glm.solve``, ``kmeans.fit`` > ``kmeans.init``,
``pca.fit`` > ``pca.factor`` ...),
attributes on those spans (``passes``, ``trials``, ``rounds`` ...) and,
in the configurations, the XLA module names of the solve's programs
(``jit__admm_run``, ``jit__lloyd_loop_fn`` ...).  A rename on the
program's side turns a metric to ``null`` on the ledger, and the first
thing to notice would be a chip run.  So every such metric of every
cell is read here from a small CPU fit of the cell's own configuration,
through the benchmark's own reader, and every program a configuration
names must be one that fit compiled and ran.

Reads ``BENCHMARK.json``, ``benchmarks/configs/`` and
``benchmarks/layer_metrics/``; a metric or a cell a later PR adds is a
case here without an edit.  What a reader returns on the CPU is a count
or a host duration of a 4,000-row fit: that it is a number is the
assertion, never its size.
"""

import functools
import importlib
import importlib.util
import json
import logging
import numbers
import os
import re

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 4000


def _load(*parts):
    with open(os.path.join(REPO, *parts), encoding="utf-8") as fh:
        return json.load(fh)


BENCH = _load("BENCHMARK.json")
CONFIGS = {c["name"]: _load(c["file"]) for c in BENCH["configs"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}

#: (metric, cell): what the program itself hands the benchmark
PROGRAM_METRICS = [
    (m["name"], cell)
    for m in BENCH["per_layer"]
    if m["source"] in ("program_span", "program_counter")
    for cell in m.get("workloads", ())
]
#: (configuration, XLA module name) the device-trace metrics sum over
NAMED_PROGRAMS = sorted({
    (name, module)
    for name, cfg in CONFIGS.items()
    for key in ("solve_modules", "init_modules", "factor_modules",
                "sweep_modules")
    for module in cfg.get(key, ())
})


def _table(cfg, rows):
    """Two classes through a logistic model, eight far blobs, or a
    Gaussian with a decaying spectrum off the origin: the kind of table
    the configuration's generator makes, small."""
    rng = np.random.RandomState(0)
    d = int(cfg["features"])
    if cfg["estimator"].endswith("PCA"):
        X = rng.normal(size=(rows, d)) * np.geomspace(4.0, 0.25, d)
        return (X + rng.uniform(-10.0, 10.0, size=d)).astype(np.float32), None
    if cfg["estimator"].endswith("KMeans"):
        centres = rng.uniform(-10.0, 10.0, size=(8, d))
        X = centres[rng.randint(8, size=rows)] + rng.normal(size=(rows, d))
        return X.astype(np.float32), None
    X = rng.normal(size=(rows, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) + rng.logistic(size=rows) > 0)
    return X, y.astype(np.float32)


def _fit(cfg, rows=ROWS):
    """One fit of the configuration's estimator with its arguments, as
    ``benchmarks/run.py :: run_cell`` makes it, on the small table."""
    from dask_ml_tpu.core import shard_rows

    module, _, attr = cfg["estimator"].rpartition(".")
    make = getattr(importlib.import_module(module), attr)
    args = json.loads(json.dumps(cfg["estimator_args"]).replace(
        '"$seed"', "0"))
    X, y = _table(cfg, rows)
    est = make(**args)
    est.fit(shard_rows(X)) if y is None else est.fit(
        shard_rows(X), shard_rows(y))
    return est


class _CompiledModules(logging.Handler):
    """Collects the XLA module names jax lowers while attached: it logs
    ``Compiling jit(<fn>) with global shapes ...`` once for each new
    (function, shapes), before any cache of executables is asked."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names = set()

    def emit(self, record):
        if str(record.msg).startswith("Compiling %s with global shapes"):
            # "jit(_admm_run)" is the module XLA calls "jit__admm_run"
            self.names.add(re.sub(r"\W", "_", record.args[0]).rstrip("_"))


@functools.cache
def _modules_of_a_cold_fit(config):
    """The modules one fit of ``config`` compiles, at a row count no
    other fit of this process has (so each is lowered, and logged,
    here); fitted once a process."""
    rows = ROWS + 8 * (1 + sorted(CONFIGS).index(config))
    # a program whose shapes do not follow the rows (the PCA's (d, d)
    # spectrum) was compiled by an earlier fit of this process: every
    # executable is dropped, so that this fit lowers each anew
    import jax

    from dask_ml_tpu.programs import cache

    for program in list(cache._BY_NAME.values()):
        program.clear()
    jax.clear_caches()
    log = logging.getLogger("jax._src.interpreters.pxla")
    seen, level = _CompiledModules(), log.level
    log.addHandler(seen)
    log.setLevel(logging.DEBUG)
    try:
        _fit(CONFIGS[config], rows)
    finally:
        log.setLevel(level)
        log.removeHandler(seen)
    return frozenset(seen.names)


def _reader(metric):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "seam_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("metric, cell", PROGRAM_METRICS)
def test_reader_finds_its_span_and_count(metric, cell):
    """A renamed span, or a count no longer put on it, reads None here
    before it reads ``null`` on the ledger."""
    from dask_ml_tpu import obs

    assert obs.enabled()
    cfg = CONFIGS[CELLS[cell]["config"]]
    _fit(cfg)  # its root is the newest: the one the reader takes
    ctx = {"trace": {"fits": [{}]}, "cell": {"config_data": cfg}}
    value = _reader(metric).read(ctx)
    assert isinstance(value, numbers.Real) and np.isfinite(value), (
        f"{metric} of {cell}: the reader found no number in the fit's "
        f"span tree: {obs.span_tree()}")


@pytest.mark.parametrize("config, module", NAMED_PROGRAMS)
def test_named_program_is_one_the_fit_runs(config, module):
    """``solve.program_ms``, ``fit.nonsolve_ms`` and the roofline shares
    sum device time by these names: one the fit never runs counts
    nothing, without an error."""
    names = _modules_of_a_cold_fit(config)
    assert module in names, (
        f"{config} names {module!r}; its fit compiled {sorted(names)}")
