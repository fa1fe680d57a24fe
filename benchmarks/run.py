"""Run one cell of ``BENCHMARK.json`` once, in this process, on this machine.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (``configs/<name>.json``) under a traffic mix
(``traffic/<name>.json``).  The run refuses a machine without a TPU whose
``device_kind`` is in ``peaks.json``, makes the table on the device from
``--seed``, warms up, loops whole ``fit`` calls through the public API for
``--seconds``, compares every fitted answer with the plain reference, and
prints one JSON line last on standard output.  With ``--trace 0`` the line
carries the end-to-end metrics, with ``--trace 1`` the per-layer metrics,
each read by its own file under ``layer_metrics/``.  See ``README.md``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as this file can see

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Books:
    """Compile events as jax itself reports them (``jax.monitoring``):
    backend compiles with their seconds, and how many of those were
    answered by the persistent compilation cache.  A copy of
    ``chip_smoke.py``'s, kept here so that the yardstick owns it."""

    def __init__(self, monitoring):
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == _COMPILE:
            with self._lock:
                self.compiles += 1
                self.compile_s += float(duration)

    def _event(self, event, **_kw):
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.compile_s, self.cache_hits


def peak_bytes(devices):
    """Per device ``peak_bytes_in_use`` (None where the backend keeps no
    memory statistics, as the CPU does)."""
    stats = [d.memory_stats() for d in devices]
    return [None if s is None else s.get("peak_bytes_in_use") for s in stats]


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, found by the name a data file or
    ``BENCHMARK.json`` gives (names may hold dots and dashes)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str) -> dict:
    """The cell with its configuration, traffic and metric lists."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r}; have {sorted(cells)}")
    cell = dict(cells[workload])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_data"] = load_json(ROOT, entry["file"])
    cell["traffic_data"] = load_json(HERE, "traffic", cell["traffic"] + ".json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


def require_chip(jax, chips: int, peaks: dict):
    """The devices of this run, or an exit without a result where there
    is no TPU in the peaks table or fewer chips than the cell asks for.
    The platform is pinned: left open, jax falls back to the CPU."""
    jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"run.py: no TPU here: {e}")
    kind = devices[0].device_kind
    if devices[0].platform != "tpu" or kind not in peaks:
        raise SystemExit(f"run.py: device {devices[0].platform}/{kind!r} is "
                         f"not in peaks.json ({sorted(peaks)})")
    if len(devices) < chips:
        raise SystemExit(f"run.py: the cell asks for {chips} chips, "
                         f"jax sees {len(devices)}")
    return devices[:chips]


def seed_key(jax, seed: int):
    """Any whole number up to a little over 2**31 (and beyond)."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def substitute_seed(args, seed: int):
    """``"$seed"`` in a configuration's estimator arguments becomes the
    run's seed, folded into what ``random_state`` takes."""
    if isinstance(args, dict):
        return {k: substitute_seed(v, seed) for k, v in args.items()}
    return seed % (2**31 - 1) if args == "$seed" else args


def row_sharding(devices):
    """``ndim -> NamedSharding``: rows over the cell's devices, every
    other axis whole on each."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(devices), ("rows",))
    return lambda ndim: NamedSharding(
        mesh, PartitionSpec("rows", *([None] * (ndim - 1))))


def import_attr(path: str):
    module, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def fetch_answer(np, est, names):
    """The fitted parameters, brought to the host: this ends a fit."""
    return {n: np.asarray(getattr(est, n)) for n in names}


def answer_digest(answer: dict) -> str:
    h = hashlib.sha256()
    for name in sorted(answer):
        h.update(name.encode())
        h.update(answer[name].tobytes())
    return h.hexdigest()


def judge(reference, ref, data, answers, last, limits):
    """Every fitted answer of the window against the reference: for each
    number compared, the worst reading over the fits beside its limit.
    Equal answers (the fits repeat one problem) are compared once."""
    worst: dict = {}
    seen = set()
    # the last fit first: it alone still has what was kept on the device
    for i in reversed(range(len(answers))):
        digest = answer_digest(answers[i])
        if digest in seen:
            continue
        seen.add(digest)
        numbers = reference.compare(
            ref, data, answers[i], last if i == len(answers) - 1 else {})
        for name, value in numbers.items():
            worst[name] = max(worst.get(name, 0.0), float(value))
    checks = {}
    for name, limit in limits.items():
        value = worst.get(name, float("inf"))
        checks[name] = {"value": value, "limit": limit,
                        "ok": bool(value <= limit)}
    return checks


def run_cell(cell, seed, seconds, trace, *, devices, peaks,
             rows_per_chip=None, estimator=None, trace_dir=None):
    """One run of one cell on ``devices``; returns the result line.

    ``rows_per_chip`` and ``estimator`` are for ``control.py`` and the
    tests under ``tests/``, which put a control or a broken estimator in
    the program's place under this same path, or run a small table on the
    CPU; a benchmark run passes neither."""
    import jax
    import numpy as np

    phases = {"to_devices_s": time.perf_counter() - T_START}
    books = Books(jax.monitoring)
    import dask_ml_tpu  # noqa: F401  (arms the compile cache at import)
    from dask_ml_tpu import diagnostics
    from dask_ml_tpu.core import device_mesh, set_mesh, shard_rows

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    chips = len(devices)
    # the program's mesh is its public setting: the cell's chips and no
    # others, also on a host that holds more
    set_mesh(device_mesh(chips))
    rows = int(rows_per_chip or cfg["rows_per_chip"]) * chips
    est_args = substitute_seed(cfg["estimator_args"], seed)
    make_est = estimator or import_attr(cfg["estimator"])
    generator = load_module("generators", cfg["generator"])
    reference = load_module("references", cfg["reference"])
    counts = load_module("counts", cfg["counts"])

    # ---- set-up: the table, born on the device from the seed ----------
    phases["import_s"] = time.perf_counter() - T_START - phases["to_devices_s"]
    t0 = time.perf_counter()
    data = generator.make(seed_key(jax, seed), rows, cfg["generator_params"],
                          row_sharding(devices))
    jax.block_until_ready([v for v in (data["X"], data["y"]) if v is not None])
    phases["generate_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    sX = shard_rows(data["X"])
    sy = None if data["y"] is None else shard_rows(data["y"])
    jax.block_until_ready([s.data for s in (sX, sy) if s is not None])
    ingest_s = time.perf_counter() - t0

    def one_fit():
        """A whole fit on a new estimator, ended by the fetch."""
        with jax.profiler.TraceAnnotation("bench.fit"):
            est = make_est(**est_args)
            est.fit(sX, sy) if sy is not None else est.fit(sX)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                answer = fetch_answer(np, est, cfg["fetch"])
        return est, answer

    t0 = time.perf_counter()
    for _ in range(int(traffic["warmup_fits"])):
        one_fit()
    phases["warmup_s"] = time.perf_counter() - t0

    def compiled():
        return (books.snapshot()[0],
                diagnostics.program_report()["totals"]["misses"])

    # ---- the window ----------------------------------------------------
    tracing = False
    if trace:
        trace_dir = trace_dir or os.path.join(HERE, ".trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tracing = True
    compiled_before = compiled()
    fits, answers, failed, est = [], [], 0, None
    traced_until, untimed = None, 0.0
    w0 = time.perf_counter()
    setup_s = w0 - T_START
    while True:
        t0 = time.perf_counter()
        try:
            est, answer = one_fit()
        except Exception:  # a failed fit is counted, and ends the window
            traceback.print_exc()
            failed += 1
            break
        t1 = time.perf_counter()
        fits.append((t0, t1))
        answers.append(answer)
        with jax.profiler.TraceAnnotation("bench.between"):
            if tracing and t1 - w0 >= float(traffic["trace_seconds"]):
                jax.profiler.stop_trace()  # not part of any fit
                tracing, traced_until = False, t1
                untimed += time.perf_counter() - t1
            done = time.perf_counter() - w0 - untimed >= seconds
        if done:
            break
    w1 = time.perf_counter()
    if tracing:
        jax.profiler.stop_trace()
        traced_until = fits[-1][1] if fits else w1
    compiled_after = compiled()
    peaks_b = peak_bytes(devices)  # before the reference touches the chip

    counters = {
        "window_compiles": sum(compiled_after) - sum(compiled_before),
        "ingest_s": ingest_s,
        "rounds": (None if est is None
                   else int(np.asarray(getattr(est, cfg["rounds_attr"])).max())),
        "fit_walls_s": [b - a for a, b in fits],
        "window_s": w1 - w0 - untimed,
        "compiles_total": books.snapshot(),
    }
    last = ({} if est is None else
            {n: getattr(est, n) for n in cfg.get("keep_last", [])})

    # ---- correct: every answer against the plain reference -------------
    del est, sX, sy  # the program's state goes before the reference runs
    t0 = time.perf_counter()
    ref = reference.build(data, est_args)
    checks = judge(reference, ref, data, answers, last, cfg["limits"])
    reference_s = time.perf_counter() - t0
    correct = bool(fits) and failed == 0 and all(
        c["ok"] for c in checks.values())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips,
              "memory_peak_bytes": max((p or 0) for p in peaks_b)}
    n_fits = len(fits)
    metrics: dict = {}
    result = {"correct": correct, "attempted": n_fits + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if not trace:
        values = {
            "fit_s": counters["window_s"] / n_fits if n_fits else None,
            "peak_hbm_gib": device["memory_peak_bytes"] / 2**30,
            "setup_s": setup_s,
        }
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        import trace as trace_reduction  # benchmarks/trace.py

        summary = trace_reduction.reduce_dir(
            trace_dir, window=("bench.fit", "bench.fetch", "bench.between"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        features = int(data["X"].shape[1])
        ctx = {
            "trace": summary, "counters": counters, "cell": cell,
            "peaks": peaks[device["kind"]], "chips": chips,
            "least": counts.per_round(rows // chips, features, est_args),
        }
        for m in cell["per_layer"]:
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
    result["extra"] = {
        "seed": seed, "rows": rows, "fits": n_fits,
        # each fit's wall: a run that reads slow shows here whether one
        # fit waited (the host stood still) or all of them were slower
        "fit_walls_s": counters["fit_walls_s"],
        "reference_s": reference_s, "ingest_s": ingest_s,
        "compiles": dict(zip(("count", "seconds", "cache_hits"),
                             counters["compiles_total"])),
        "setup_s": setup_s, "setup_phases": phases,
    }
    # last of all, each number compared beside its limit
    result["checks"] = {k: [c["value"], c["limit"]] for k, c in checks.items()}
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    print(f"correct: {correct} ({n_fits} fits, {failed} failed)",
          file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)  # trace.py
    sys.path.insert(0, ROOT)  # dask_ml_tpu, from this checkout
    cell = load_cell(args.workload)
    peaks = load_json(HERE, "peaks.json")["peaks"]
    import jax

    devices = require_chip(jax, int(cell["chips"]), peaks)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
