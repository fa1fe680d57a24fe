"""Host IO: native multithreaded CSV / raw-float32 ingest.

The reference delegates file ingest to dask.dataframe/array readers
(external; pandas C parser under the hood).  Here the loader is an in-repo
C++ shim (``native/loader.cpp``, built on first use with the system g++)
driven through ctypes — no Python-level tokenization on the ingest path —
plus generators that stream row blocks straight into ``shard_rows`` /
``wrappers.Incremental``.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import threading

from ._locks import make_lock

import numpy as np

__all__ = [
    "read_csv",
    "read_binary",
    "stream_csv_blocks",
    "stream_binary_blocks",
    "read_csv_sharded",
    "stream_text_lines",
    "stream_dataset",
    "to_columnar",
]

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native",
                    "loader.cpp")

_lock = make_lock("io.registry")
_lib = None


def _so_path() -> str:
    """The shared object for the CURRENT ``loader.cpp``: its name carries
    a digest of the source's content, so a stale binary (an mtime says
    nothing after a copy or a checkout) can never be the one loaded."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(_SRC), f"_loader-{digest}.so")


def _build(so: str) -> None:
    # build beside the target and rename: a concurrent process (multihost
    # workers share the checkout) must never dlopen a half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    except FileNotFoundError as e:  # pragma: no cover
        raise RuntimeError("native loader needs g++ on PATH") from e
    except subprocess.CalledProcessError as e:  # pragma: no cover
        raise RuntimeError(f"native loader build failed:\n{e.stderr}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # binaries built from any other source text are dead weight
    for stale in glob.glob(os.path.join(os.path.dirname(so), "_loader*.so")):
        if stale != so:
            with contextlib.suppress(OSError):
                os.unlink(stale)


def _load():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        lib = ctypes.CDLL(so)
        lib.dmlt_csv_dims.argtypes = [
            ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dmlt_csv_dims.restype = ctypes.c_int
        lib.dmlt_csv_read_f32.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_float), ctypes.c_int,
        ]
        lib.dmlt_csv_read_f32.restype = ctypes.c_int
        lib.dmlt_bin_read_f32.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.dmlt_bin_read_f32.restype = ctypes.c_int
        lib.dmlt_stream_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
        ]
        lib.dmlt_stream_open.restype = ctypes.c_void_p
        lib.dmlt_stream_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dmlt_stream_next.restype = ctypes.c_int
        lib.dmlt_stream_close.argtypes = [ctypes.c_void_p]
        lib.dmlt_stream_close.restype = None
        _lib = lib
        return lib


def _check(rc: int, path: str) -> None:
    if rc != 0:
        raise OSError(-rc, os.strerror(-rc) if -rc < 200 else "parse error", path)


def csv_dims(path: str, *, has_header: bool = False) -> tuple[int, int]:
    """(rows, cols) of a numeric CSV, excluding the header if present."""
    lib = _load()
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.dmlt_csv_dims(path.encode(), int(has_header), ctypes.byref(rows), ctypes.byref(cols))
    _check(rc, path)
    return rows.value, cols.value


def read_csv(path: str, *, has_header: bool = False,
             n_threads: int | None = None, retries: int = 0,
             retry_backoff: float = 0.1,
             retry_deadline_s: float | None = 120.0,
             retry_budget=None) -> np.ndarray:
    """Parse a numeric CSV into a float32 (rows, cols) array, one parser
    thread per row range.

    ``retries`` re-attempts the whole parse on a transient fault
    (flaky network filesystem, contended mount) with exponential backoff
    via :func:`dask_ml_tpu.resilience.retry` — absorbed faults and
    propagated failures are both counted in the global
    :func:`~dask_ml_tpu.diagnostics.fault_stats` under the ``"ingest"``
    tag, so recovery is observable, never silent.  ``retry_deadline_s``
    wall-clock-bounds the retry loop (the re-attempt budget is caller
    input, so the bound must not depend on it — graftlint's
    ``unbounded-retry`` contract): a persistently failing mount raises
    :class:`~dask_ml_tpu.resilience.DeadlineExceeded` loudly instead of
    backing off for as long as the budget arithmetic allows.
    ``retry_budget`` optionally shares a per-fit
    :class:`~dask_ml_tpu.resilience.FaultBudget` with the other fault
    points of the calling fit (design.md §13) — cascading ingest faults
    then stop at the fit-wide ceiling, not this site's alone.
    """
    from .resilience.retry import retry as _retry
    from .resilience.testing import maybe_fault

    def _parse():
        maybe_fault("ingest")
        lib = _load()
        rows, cols = csv_dims(path, has_header=has_header)
        out = np.empty((rows, cols), dtype=np.float32)
        nt = n_threads or min(32, os.cpu_count() or 1)
        rc = lib.dmlt_csv_read_f32(
            path.encode(), int(has_header), 0, rows, cols,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), int(nt),
        )
        _check(rc, path)
        return out

    return _retry(_parse, retries=int(retries), backoff=retry_backoff,
                  deadline=retry_deadline_s, budget=retry_budget,
                  tag="ingest")


def read_binary(path: str, shape: tuple[int, ...], *,
                offset_bytes: int = 0) -> np.ndarray:
    """Read raw little-endian float32 into the given shape."""
    lib = _load()
    out = np.empty(shape, dtype=np.float32)
    rc = lib.dmlt_bin_read_f32(
        path.encode(), int(offset_bytes), int(out.size),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    _check(rc, path)
    return out


def stream_csv_blocks(path: str, block_rows: int, *, has_header: bool = False,
                      n_threads: int | None = None, prefetch: int = 2,
                      retries: int = 0, retry_backoff: float = 0.1,
                      retry_deadline_s: float | None = 120.0,
                      retry_budget=None):
    """Yield float32 row blocks of (at most) ``block_rows`` — the
    out-of-core ingest feeding ``wrappers.Incremental`` (the reference's
    sequential block streaming, SURVEY.md §2.2).

    Backed by the native WINDOWED streaming session: the file moves
    through a ~32 MB window (never fully resident — host RSS is bounded
    no matter the file size: a 2 GB stream measures ~494 MB peak
    including the jax runtime, and a 12 GB stream asserts < 1.5 GB —
    tests/test_streaming_rss.py) while a background C++ worker parses
    ``prefetch`` blocks ahead of the consumer, so parsing overlaps the
    device compute consuming the blocks.

    ``retries`` re-attempts each BLOCK fetch on a transient fault with
    exponential backoff (:func:`dask_ml_tpu.resilience.retry`, tag
    ``"ingest"``) — the native session keeps the stream position, so a
    failed attempt never skips rows.  ``retry_deadline_s`` wall-clock
    bounds each block's retry loop (see :func:`read_csv`)."""
    if block_rows < 1:
        raise ValueError(f"block_rows must be >= 1, got {block_rows}")
    from .resilience.retry import retry as _retry
    from .resilience.testing import maybe_fault

    lib = _load()
    n_threads = n_threads or min(8, os.cpu_count() or 1)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    err = ctypes.c_int()
    handle = lib.dmlt_stream_open(
        path.encode(), int(has_header), int(block_rows), int(n_threads),
        int(max(prefetch, 1)), ctypes.byref(rows), ctypes.byref(cols),
        ctypes.byref(err),
    )
    if not handle:
        _check(err.value, path)
    try:
        c = cols.value
        got = ctypes.c_int64()

        def _next_block():
            maybe_fault("ingest")
            # fresh buffer per block: the native memcpy fills it and the
            # trimmed view is yielded as-is — no second Python-side copy
            buf = np.empty((block_rows, max(c, 1)), dtype=np.float32)
            rc = lib.dmlt_stream_next(
                handle, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                ctypes.byref(got),
            )
            _check(rc, path)
            return buf

        while True:
            buf = _retry(_next_block, retries=int(retries),
                         backoff=retry_backoff,
                         deadline=retry_deadline_s, budget=retry_budget,
                         tag="ingest")
            if got.value == 0:
                break
            yield buf[: got.value]
    finally:
        lib.dmlt_stream_close(handle)


def stream_binary_blocks(path: str, block_rows: int, n_features: int, *,
                         n_rows: int | None = None, offset_bytes: int = 0,
                         retries: int = 0, retry_backoff: float = 0.1,
                         retry_deadline_s: float | None = 120.0,
                         retry_budget=None):
    """Yield float32 row blocks of (at most) ``block_rows`` from a raw
    little-endian float32 file — the binary twin of
    :func:`stream_csv_blocks`, for out-of-core streams whose parse cost
    is pure disk read.

    ``n_rows`` defaults to every complete row after ``offset_bytes``
    (the file may carry a trailing partial row, e.g. an interrupted
    writer — it is ignored, matching the complete-blocks contract).
    Feed the generator to ``_partial.fit`` / ``wrappers.Incremental`` to
    ride the prefetch pipeline (:mod:`dask_ml_tpu.pipeline`): block
    *k+1*'s read + H2D staging then overlaps block *k*'s device step.

    ``retries`` re-attempts each BLOCK read on a transient fault
    (:func:`dask_ml_tpu.resilience.retry`, tag ``"ingest"``); reads are
    offset-addressed, so a failed attempt never skips rows.
    ``retry_deadline_s`` wall-clock bounds each block's retry loop (see
    :func:`read_csv`).
    """
    if block_rows < 1:
        raise ValueError(f"block_rows must be >= 1, got {block_rows}")
    if n_features < 1:
        raise ValueError(f"n_features must be >= 1, got {n_features}")
    if offset_bytes < 0:
        raise ValueError(f"offset_bytes must be >= 0, got {offset_bytes}")
    row_bytes = 4 * int(n_features)
    try:
        total = os.path.getsize(path)
    except OSError as e:
        raise OSError(e.errno or 2, e.strerror or "stat failed", path)
    if n_rows is None:
        n_rows = max(total - int(offset_bytes), 0) // row_bytes
    n_rows = int(n_rows)
    # up-front extent validation, EAGER (this wrapper runs at call time,
    # not first next()): a truncated file must fail HERE, not as a short
    # read in the middle of an epoch — mid-stream the model has already
    # trained on a partial pass, the worst failure shape
    need = int(offset_bytes) + n_rows * row_bytes
    if need > total:
        raise ValueError(
            f"{path}: {n_rows} rows x {n_features} float32 features at "
            f"offset {offset_bytes} needs {need} bytes, file has {total} "
            f"— truncated file or wrong shape")

    def _blocks():
        from .resilience.retry import retry as _retry
        from .resilience.testing import maybe_fault

        def _read_block(lo, rows):
            maybe_fault("ingest")
            return read_binary(
                path, (rows, int(n_features)),
                offset_bytes=int(offset_bytes) + lo * row_bytes,
            )

        for lo in range(0, n_rows, int(block_rows)):
            rows = min(int(block_rows), n_rows - lo)
            yield _retry(_read_block, lo, rows, retries=int(retries),
                         backoff=retry_backoff, deadline=retry_deadline_s,
                         budget=retry_budget, tag="ingest")

    return _blocks()


def stream_text_lines(path: str, block_lines: int = 10_000, *,
                      retries: int = 0, retry_backoff: float = 0.1,
                      retry_deadline_s: float | None = 120.0,
                      retry_budget=None):
    """Yield lists of (at most) ``block_lines`` stripped text lines —
    out-of-core text ingest feeding the streaming vectorizers
    (``feature_extraction.text.*.stream_transform``): the file is read
    incrementally, never whole.

    ``retries`` re-attempts each BLOCK read on a transient fault with
    exponential backoff (:func:`dask_ml_tpu.resilience.retry`, tag
    ``"ingest"`` — the PR-4 ingest contract the numeric streams already
    carry): reads are byte-offset-addressed, so a failed attempt
    reopens, seeks to the block's start, and re-reads exactly the same
    lines — nothing skipped, nothing repeated.  ``retry_deadline_s``
    wall-clock-bounds each block's retry loop and ``retry_budget``
    optionally shares the fit-wide
    :class:`~dask_ml_tpu.resilience.FaultBudget` (see
    :func:`read_csv`)."""
    if block_lines < 1:
        raise ValueError(f"block_lines must be >= 1, got {block_lines}")
    from .resilience.retry import retry as _retry
    from .resilience.testing import maybe_fault

    state: dict = {"pos": 0, "f": None}

    def _read_block():
        maybe_fault("ingest")
        f = state["f"]
        if f is None or f.closed:
            f = state["f"] = open(path, "r", encoding="utf-8")
        f.seek(state["pos"])
        # readline (not iteration): line iteration read-ahead makes
        # tell() illegal, and the saved offset is the retry contract
        block: list[str] = []
        while len(block) < block_lines:
            line = f.readline()
            if not line:
                break
            block.append(line.rstrip("\n"))
        state["pos"] = f.tell()
        return block

    try:
        while True:
            block = _retry(_read_block, retries=int(retries),
                           backoff=retry_backoff,
                           deadline=retry_deadline_s, budget=retry_budget,
                           tag="ingest")
            if not block:
                break
            yield block
    finally:
        if state["f"] is not None and not state["f"].closed:
            state["f"].close()


def read_csv_sharded(path: str, *, has_header: bool = False, mesh=None,
                     retries: int = 0, retry_backoff: float = 0.1):
    """Parse a CSV and place it row-sharded over the mesh (ShardedRows)."""
    from .core.sharded import shard_rows

    return shard_rows(
        read_csv(path, has_header=has_header, retries=retries,
                 retry_backoff=retry_backoff),
        mesh,
    )


#: file suffixes ``to_columnar`` treats as raw float32 (anything else
#: parses as CSV)
_BINARY_SUFFIXES = (".bin", ".raw", ".f32")


def to_columnar(path: str, out_dir: str, *, source: str = "auto",
                n_features: int | None = None, has_header: bool = False,
                label_col: int | None = None, shards: int = 4,
                block_rows: int = 4096, compression: str = "zlib"):
    """Convert a CSV or raw-float32 file into a sharded columnar
    dataset directory (:mod:`dask_ml_tpu.data`) — one streaming pass,
    bounded memory, bucket-aligned blocks.

    The columnar form is what repeated epochs should stream: parse cost
    is paid ONCE here instead of per epoch, blocks are individually
    addressable (the key-derived shuffle and reader replay need that),
    and ``block_rows`` (default 4096, an ``auto`` ladder rung) makes
    ``programs.bucket.pad_block`` a no-op on the hot path.
    ``label_col`` splits that column off as the target ``y``.
    Returns the :class:`~dask_ml_tpu.data.DatasetManifest`.
    """
    from . import data as _data

    if source == "auto":
        source = "binary" if path.lower().endswith(_BINARY_SUFFIXES) \
            else "csv"
    if source == "csv":
        return _data.convert_csv(
            out_dir=out_dir, path=path, has_header=has_header,
            label_col=label_col, shards=shards, block_rows=block_rows,
            compression=compression)
    if source == "binary":
        if n_features is None:
            raise ValueError(
                "to_columnar needs n_features for a raw binary source")
        return _data.convert_binary(
            out_dir=out_dir, path=path, n_features=int(n_features),
            label_col=label_col, shards=shards, block_rows=block_rows,
            compression=compression)
    raise ValueError(
        f"source must be 'auto', 'csv', or 'binary', got {source!r}")


def stream_dataset(path, **kwargs):
    """Open a sharded columnar dataset (a manifest path / dataset
    directory / :class:`~dask_ml_tpu.data.DatasetManifest`) as a
    :class:`~dask_ml_tpu.data.ShardedDataset` — the parallel-reader,
    key-shuffled successor of the single-stream ``stream_*_blocks``
    generators; feed it to ``_partial.fit`` / ``wrappers.Incremental``
    / ``pipeline.stream_partial_fit`` directly."""
    from .data import ShardedDataset

    return ShardedDataset(path, **kwargs)
