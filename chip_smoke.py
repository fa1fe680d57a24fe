"""Fit, stream and serve one HIGGS-width (28-feature) model on the chip.

The quickest proof that the system still starts on a TPU: one process,
public API only, every policy knob at its default.  Three phases, the
life of one model (``BASELINE.json`` configurations 1 and 4):

* *fit*: 11,000,000x28 float32 from a seed, ``shard_rows``,
  ``LogisticRegression(solver="admm")``, ``score`` on the device;
* *stream*: a labelled CSV -> ``io.to_columnar`` (builds and drives
  ``native/loader.cpp``) -> ``Incremental(SGDClassifier())`` over a
  4-reader ``data.ShardedDataset``;
* *serve*: that SGD model behind a ``ModelServer``, 4 client threads,
  every answer compared with the estimator's own, no compile after
  ``load`` returns.

Any exception or failed check ends the run non-zero and prints no result
line.  Without an accelerator the run fails before any work; a CPU run
at toy size happens only under ``--rehearsal`` and says so in its
output.  Standard output is two JSON lines: the report (versions, cache
directory, resolved arms, per-phase seconds, compiles and peak bytes),
then the verdict, ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with the device as jax reports it and no other key.

    python chip_smoke.py              # on the chip
    python chip_smoke.py --rehearsal  # here, on the CPU, tiny
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

D = 28  # HIGGS width; never narrowed

#: (fit rows, stream rows, stream block rows) — full size and rehearsal
SIZES = {False: (11_000_000, 262_144, 16_384), True: (20_000, 8_192, 1_024)}

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def check(ok: bool, what: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class Books:
    """Compile events as jax itself reports them (``jax.monitoring``):
    backend compiles with their seconds, and how many of those were
    answered by the persistent compilation cache."""

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


def phase_fit(jax, np, n_rows):
    from dask_ml_tpu.core import shard_rows
    from dask_ml_tpu.linear_model import LogisticRegression

    rng = np.random.default_rng(0)
    X = rng.standard_normal((n_rows, D), dtype=np.float32)
    w = rng.standard_normal(D, dtype=np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w)))
    y = (rng.random(n_rows, dtype=np.float32) < p).astype(np.float32)

    t0 = time.perf_counter()
    sX, sy = shard_rows(X), shard_rows(y)
    jax.block_until_ready((sX.data, sy.data))
    ingest_s = time.perf_counter() - t0

    devices = jax.devices()
    shards = sX.data.addressable_shards
    rows = sorted(s.data.shape[0] for s in shards)
    check(len(shards) == len(devices)
          and {s.device for s in shards} == set(devices),
          f"shard_rows placed {len(shards)} shards on {len(devices)} devices")
    check(rows[0] == rows[-1] == sX.data.shape[0] // len(devices),
          f"rows per device are uneven: {rows}")

    lr = LogisticRegression(solver="admm", C=1e4, max_iter=10,
                            solver_kwargs={"inner_iter": 30})
    t0 = time.perf_counter()
    lr.fit(sX, sy)
    jax.block_until_ready(lr.coef_)
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    acc = float(lr.score(sX, sy))  # one replicated scalar leaves the device
    score_s = time.perf_counter() - t0

    coef = np.asarray(lr.coef_)
    check(isinstance(lr.coef_, jax.Array)
          and {d.platform for d in lr.coef_.devices()}
          == {devices[0].platform},
          "coef_ is not resident on the accelerator")
    check(coef.shape == (D,) and bool(np.isfinite(coef).all())
          and np.isfinite(lr.intercept_), "coef_ is not finite (28,)")
    check(acc >= 0.85, f"ADMM train accuracy {acc:.4f} < 0.85")
    return {
        "rows": n_rows, "rows_per_device": rows[0],
        "ingest_s": round(ingest_s, 3), "fit_s": round(fit_s, 3),
        "score_s": round(score_s, 3), "accuracy": round(acc, 4),
        "n_iter": int(lr.n_iter_[0]),
    }


def phase_stream(np, n_rows, block_rows, workdir):
    from dask_ml_tpu import data, diagnostics, io
    from dask_ml_tpu.linear_model import SGDClassifier
    from dask_ml_tpu.wrappers import Incremental

    rng = np.random.default_rng(1)
    X = rng.standard_normal((n_rows, D), dtype=np.float32)
    y = (X @ rng.standard_normal(D, dtype=np.float32) > 0).astype(np.int32)
    csv = os.path.join(workdir, "higgs_like.csv")
    np.savetxt(csv, np.column_stack([X, y]), fmt="%.7g", delimiter=",")
    ds_dir = os.path.join(workdir, "columnar")
    t0 = time.perf_counter()
    manifest = io.to_columnar(csv, ds_dir, label_col=D, shards=4,
                              block_rows=block_rows)
    convert_s = time.perf_counter() - t0
    check(manifest.n_shards == 4, f"{manifest.n_shards} shards, wanted 4")

    diagnostics.reset_pipeline_stats()
    inc = Incremental(SGDClassifier())
    t0 = time.perf_counter()
    inc.fit(data.ShardedDataset(ds_dir, key=0, epochs=2, readers=4),
            classes=[0, 1])
    coef = np.asarray(inc.estimator_.coef_)  # the fetch waits for the chain
    fit_s = time.perf_counter() - t0
    acc = float(inc.score(X, y))
    bucket = diagnostics.pipeline_report()["cumulative"]["bucket"]

    check(coef.shape == (1, D) and bool(np.isfinite(coef).all()),
          "streamed coef_ is not finite (1, 28)")
    check(acc >= 0.8, f"streamed SGD accuracy {acc:.4f} < 0.8")
    check(bucket["blocks"] == 2 * n_rows // block_rows
          and bucket["padded_blocks"] == 0,
          f"bucket books {bucket}: every block should arrive pad-free")
    return inc.estimator_, X, {
        "rows": n_rows, "block_rows": block_rows, "epochs": 2,
        "convert_s": round(convert_s, 3), "fit_s": round(fit_s, 3),
        "accuracy": round(acc, 4), "bucket": bucket,
    }


def phase_serve(np, books, sgd, pool):
    from dask_ml_tpu import diagnostics
    from dask_ml_tpu.serve import ModelServer

    clients, per_client = 4, 16
    pool = pool[:clients * per_client * 16]
    # the estimator's own answers, taken BEFORE load so that whatever they
    # compile is not read as a compile on the request path
    want = sgd.predict(pool)
    want_p = np.asarray(sgd.predict_proba(pool))

    def compiled():
        """Program-cache misses, and every backend compile jax reports."""
        return (diagnostics.program_report()["totals"]["misses"],
                books.snapshot()[0])

    def client(c):
        rng = np.random.default_rng(100 + c)
        for i in range(per_client):
            n = int(rng.integers(1, 17))
            lo = int(rng.integers(0, len(pool) - n + 1))
            if i % 2:
                got = np.asarray(server.predict_proba("higgs", pool[lo:lo + n]))
                check(np.allclose(got, want_p[lo:lo + n], rtol=0, atol=1e-6),
                      f"served predict_proba differs at rows {lo}:{lo + n}")
            else:
                got = np.asarray(server.predict("higgs", pool[lo:lo + n]))
                check(np.array_equal(got, want[lo:lo + n]),
                      f"served predict differs at rows {lo}:{lo + n}")
        return per_client

    server = ModelServer()
    try:
        t0 = time.perf_counter()
        server.load("higgs", sgd)
        load_s = time.perf_counter() - t0
        warm = compiled()
        t0 = time.perf_counter()
        with ThreadPoolExecutor(clients) as pool_ex:
            served = sum(pool_ex.map(client, range(clients)))
        requests_s = time.perf_counter() - t0
        check(compiled() == warm,
              f"compiles after load returned: {warm} -> {compiled()} "
              "(cache misses, backend compiles)")
    finally:
        server.close()
    return {"requests": served, "load_s": round(load_s, 3),
            "requests_s": round(requests_s, 3)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; proves the script, not "
                         "the chip, and labels its output so")
    rehearsal = ap.parse_args().rehearsal

    import jax

    # pinned: with the platform left open jax falls back to the CPU with a
    # warning when libtpu cannot open the chip
    jax.config.update("jax_platforms", "cpu" if rehearsal else "tpu")
    devices = jax.devices()
    platform = devices[0].platform
    check(platform == ("cpu" if rehearsal else "tpu"),
          f"jax.devices()[0].platform is {platform!r}")
    books = Books(jax.monitoring)

    import numpy as np

    import dask_ml_tpu  # noqa: F401  (arms the compile cache at import)
    from dask_ml_tpu import diagnostics
    from dask_ml_tpu.ops import scatter_strategy
    from dask_ml_tpu.solvers import grid_pack_strategy, pack_strategy
    from dask_ml_tpu.solvers.algorithms import line_search_strategy

    n_fit, n_stream, block_rows = SIZES[rehearsal]
    phases: dict = {}

    def run(name, fn):
        before, t0 = books.snapshot(), time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        after = books.snapshot()
        stats = out[-1] if isinstance(out, tuple) else out
        stats.update(
            wall_s=round(wall, 3), compiles=after[0] - before[0],
            compile_s=round(after[1] - before[1], 3),
            persistent_cache_hits=after[2] - before[2],
            peak_bytes_in_use=peak_bytes(devices))
        phases[name] = stats
        return out

    run("fit", lambda: phase_fit(jax, np, n_fit))
    peaks = phases["fit"]["peak_bytes_in_use"]
    if len(devices) > 1 and None not in peaks:
        # the fit is the sharded phase (stream and serve stage blocks on
        # the default device only — ROADMAP D6): no device may have held
        # the whole matrix, and the shares must be even
        check(max(peaks) < n_fit * D * 4 and max(peaks) <= 1.25 * min(peaks),
              f"per-device peak bytes after the fit are uneven: {peaks}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
        sgd, pool, _ = run("stream", lambda: phase_stream(
            np, n_stream, block_rows, workdir))
    run("serve", lambda: phase_serve(np, books, sgd, pool))

    versions = {"jax": jax.__version__,
                "jaxlib": importlib.metadata.version("jaxlib"),
                "numpy": np.__version__}
    if not rehearsal:
        versions["libtpu"] = importlib.metadata.version("libtpu")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    report = {
        "rehearsal": rehearsal,
        "device": device,
        "versions": versions,
        "compile_cache_dir": diagnostics.program_report()["persistent_cache"],
        "arms": {"scatter": scatter_strategy(), "pack": pack_strategy(),
                 "grid_pack": grid_pack_strategy(),
                 "line_search": line_search_strategy()},
        "phases": phases,
    }
    print(json.dumps(report))
    # the last line is the verdict alone: exactly these keys
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
