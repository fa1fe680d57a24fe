"""Sharded dataset runtime: N supervised readers, one merged stream.

``io.stream_*`` is one generator on one thread: the prefetch worker can
hide ONE block's parse behind the device step, but the parse rate
itself is a single reader's.  This module is the scale-out half of the
ingest story (ROADMAP ``[data]``, SURVEY §7 hard part (b)): a
:class:`ShardedDataset` turns a manifest of columnar shard files into
ONE deterministic block stream produced by ``DASK_ML_TPU_DATA_READERS``
parallel reader threads and re-serialized through a bounded
reorder/merge queue —

* **order is a value, not an accident**: epoch ``e``'s visit order is
  the key-derived :func:`~.shuffle.epoch_plan` (shard order and
  intra-shard block order from ``fold_in`` chains), so the merged
  stream is IDENTICAL at every reader count, across runs, and across
  restarts — the property every equality test, A/B arm, and resume
  path in this repo leans on;
* **readers are supervised units** (domain ``"data"``, heartbeat per
  block, literal thread name ``dask-ml-tpu-data-reader`` — declared
  host-only in ``analysis.rules._spmd``: a reader parses bytes and
  NEVER touches jax): a reader death — reported fault or silent
  :class:`~..resilience.testing.ThreadCrash` caught by the consumer's
  liveness poll — is a **budgeted restart** (``supervisor.note_death``
  → ``FaultBudget.acquire("data-reader")`` → ``note_restart``): the
  replacement replays the dead reader's in-flight shard range and the
  merge queue's sequence-number dedup makes delivery exactly-once;
* **host RAM is bounded** by the reorder window
  (``DASK_ML_TPU_DATA_QUEUE`` blocks): a reader that runs ahead of the
  consumer parks on the window condition, so a fast shard cannot
  buffer itself into an OOM — there is no shuffle buffer anywhere.

The merged stream object is a plain block iterator with
``restartable_source = True`` — the opt-in contract the elastic
pipeline driver (``pipeline/core.py``) honors for parse-fault retries —
so a dataset drops into ``stream_partial_fit`` / ``_partial.fit`` /
``wrappers.Incremental`` wherever a generator did.
"""

from __future__ import annotations

import os
import threading

from .._locks import make_condition
import time

import numpy as np

from .. import obs
from ..control import knobs as _knobs
from ..obs.metrics import registry as _registry
from ..resilience import supervisor as _supervisor
from ..resilience.elastic import BudgetExhausted, FaultBudget
from ..resilience.testing import ThreadCrash as _ThreadCrash
from ..resilience.testing import maybe_fault as _maybe_fault
from .manifest import DatasetManifest
from .shuffle import as_key, epoch_plan

__all__ = [
    "READERS_ENV",
    "QUEUE_ENV",
    "READER_THREAD_NAME",
    "resolve_readers",
    "resolve_queue_blocks",
    "ShardedDataset",
]

#: policy knob: parallel reader threads per dataset stream.
READERS_ENV = "DASK_ML_TPU_DATA_READERS"

#: policy knob: reorder/merge window in blocks (bounds host RAM).
QUEUE_ENV = "DASK_ML_TPU_DATA_QUEUE"

#: the reader threads' literal name — declared HOST-ONLY by contract in
#: ``analysis.rules._spmd.HOST_ONLY_THREAD_NAMES``: graftsan's dispatch
#: detector raises in a reader that ever dispatches a device program,
#: and a steady compile attributed to one is a hard violation.
READER_THREAD_NAME = "dask-ml-tpu-data-reader"

_DEFAULT_READERS = 4

#: consumer-side poll interval: how long the merge wait blocks before
#: re-checking reader liveness (the silent-death detection latency)
_POLL_S = 0.05


def _resolve_int(env: str, default: int, what: str,
                 value: int | None = None) -> int:
    if value is None:
        raw = os.environ.get(env, "").strip()
        if raw:
            try:
                value = int(raw)
            except ValueError:
                raise ValueError(
                    f"{env} must be an integer, got {raw!r}") from None
        else:
            value = default
    value = int(value)
    if value < 1:
        raise ValueError(f"{what} must be >= 1, got {value}")
    return value


def resolve_readers(readers: int | None = None) -> int:
    """Reader-thread count: explicit argument, else the live graftpilot
    override, else the ``DASK_ML_TPU_DATA_READERS`` knob, else 4.
    Strict parse."""
    if readers is None:
        readers = _knobs.override("data_readers")
    return _resolve_int(READERS_ENV, _DEFAULT_READERS, "reader count",
                        readers)


def resolve_queue_blocks(queue_blocks: int | None = None,
                         readers: int = _DEFAULT_READERS) -> int:
    """Reorder-window size in blocks: explicit, else the live graftpilot
    override, else the ``DASK_ML_TPU_DATA_QUEUE`` knob, else
    ``2 × readers`` (deep enough that every reader can stay one block
    ahead, shallow enough that host RAM stays a handful of blocks)."""
    if queue_blocks is None:
        queue_blocks = _knobs.override("data_queue")
    return _resolve_int(QUEUE_ENV, 2 * int(readers), "queue window",
                        queue_blocks)


class ShardedDataset:
    """A manifest of columnar shards presented as one deterministic,
    supervised, parallel-read block stream (see module docstring).

    Args:
      source: a :class:`~.manifest.DatasetManifest`, or a path to one /
        to a dataset directory.
      key: shuffle key — an int seed, a ``uint32[2]`` array, or a jax
        PRNG key (``shuffle.as_key``).  Epoch ``e``'s order derives from
        ``fold_in(key, e)``.
      epochs: how many passes ``iter_blocks()`` makes (each its own
        permutation).
      shuffle: ``False`` = identity order (manifest shard order, file
        block order) — the converter-verification / sequential-scan mode.
      readers / queue_blocks: see the env-knob resolvers.
      budget: the restart :class:`~..resilience.elastic.FaultBudget`
        (default: one from ``DASK_ML_TPU_FAULT_BUDGET`` per stream) —
        every reader restart draws from it; exhaustion raises
        :class:`~..resilience.elastic.BudgetExhausted` on the consumer.
      reader_restarts: per-stream ceiling on reader restarts even under
        a generous budget (a persistently-crashing shard must fail
        loudly, not loop).
      fetch_latency_s: per-block sleep INSIDE the reader before the
        read — a remote-store emulation hook (an object-store GET has
        RTT this box's page cache does not); 0 by default.
    """

    #: the elastic pipeline contract: a pull that raised did not lose
    #: its position — the merge queue holds the stream's place, so a
    #: retried ``__next__`` resumes exactly where the fault surfaced.
    restartable_source = True

    def __init__(self, source, *, key=0, epochs: int = 1,
                 shuffle: bool = True, readers: int | None = None,
                 queue_blocks: int | None = None, start: int = 0,
                 budget: FaultBudget | None = None,
                 reader_restarts: int = 4,
                 fetch_latency_s: float = 0.0,
                 label: str = "dataset"):
        if isinstance(source, DatasetManifest):
            self.manifest = source
        else:
            self.manifest = DatasetManifest.load(source)
        if self.manifest.n_shards < 1:
            raise ValueError("dataset has no shards")
        self.key = as_key(key)
        self.epochs = int(epochs)
        if self.epochs < 1:
            raise ValueError(f"epochs must be >= 1, got {self.epochs}")
        self.shuffle = bool(shuffle)
        # explicit args PIN their value (a test that asks for readers=2
        # gets exactly 2); env/default-resolved sizing is LIVE — streams
        # re-read the graftpilot override at their natural boundaries
        # (reorder-window check per offer, reader scale-up from the
        # consumer's liveness poll) and observe the base they run with
        self._readers_pinned = readers is not None
        self._queue_pinned = queue_blocks is not None
        self.readers = resolve_readers(readers)
        self.queue_blocks = resolve_queue_blocks(queue_blocks,
                                                 self.readers)
        if not self._readers_pinned:
            _knobs.observe("data_readers", self.readers)
        if not self._queue_pinned:
            _knobs.observe("data_queue", self.queue_blocks)
        self.start = int(start)
        self.budget = budget
        self.reader_restarts = int(reader_restarts)
        self.fetch_latency_s = float(fetch_latency_s)
        self.label = str(label)

    # -- geometry ------------------------------------------------------
    @property
    def rows(self) -> int:
        return self.manifest.rows

    @property
    def n_blocks(self) -> int:
        """Blocks per epoch."""
        return self.manifest.n_blocks

    def plan(self, epoch: int):
        """The epoch's deterministic visit order (``shuffle.EpochPlan``)."""
        return epoch_plan(self.key, epoch,
                          self.manifest.blocks_per_shard(),
                          shuffle=self.shuffle)

    # -- streaming -----------------------------------------------------
    def iter_blocks(self, epoch: int | None = None, start: int | None = None):
        """The merged block stream: ``(X, y_or_None)`` tuples for 1- or
        2-column datasets (the pipeline contract), raw column tuples
        otherwise.

        ``epoch=None`` streams all ``self.epochs`` passes back to back;
        an explicit ``epoch`` streams that single pass.  ``start`` skips
        the first ``start`` blocks of the stream (counted across epochs
        for the multi-epoch form) — the ``FitCheckpoint`` resume
        contract: a fit that consumed ``k`` blocks resumes with
        ``start=k`` and replays exactly the unseen suffix."""
        start = self.start if start is None else int(start)
        if epoch is not None:
            epoch_range = [int(epoch)]
        else:
            epoch_range = list(range(self.epochs))
            skip_epochs, start = divmod(start, max(self.n_blocks, 1))
            epoch_range = epoch_range[skip_epochs:]
        return _DatasetStream(self, epoch_range, start)

    def __iter__(self):
        return self.iter_blocks()

    def __repr__(self):
        return (f"ShardedDataset({self.manifest!r}, epochs={self.epochs}, "
                f"readers={self.readers}, window={self.queue_blocks}, "
                f"shuffle={self.shuffle})")


class _DatasetStream:
    """One live merged stream over (a range of) epochs.

    The iterator the consumer holds; owns the reader threads of the
    CURRENT epoch and the reorder buffer.  All coordination lives under
    one condition variable: readers offer ``(seq, block)`` and park
    while ``seq >= next_seq + window``; the consumer delivers strictly
    at ``next_seq`` and wakes parked readers as the window slides.
    """

    restartable_source = True

    def __init__(self, ds: ShardedDataset, epoch_range, start: int):
        self._ds = ds
        self._epochs = list(epoch_range)
        self._first_start = max(int(start), 0)
        # graftpath stitching (design.md §19): the stream is opened on
        # the consuming side (as_block_source, inside the pipeline's
        # stream span) — capture that span id so the READER threads'
        # work intervals (``data.parse`` pread+decompress, ``data.fetch``
        # emulated RTT) attach under the owning stream instead of being
        # dropped as rootless; None (no open span / tracing off) keeps
        # the readers span-silent.
        self._trace_parent = obs.current_span_id()
        self._budget = ds.budget if ds.budget is not None \
            else FaultBudget.from_env(name=f"{ds.label}-readers")
        self._cond = make_condition("data.readers")
        self._closed = False
        self._epoch_live = False
        self.blocks_delivered = 0
        self.rows_delivered = 0
        self._restarts = 0
        self._threads: list = []
        self._hbs: list = []

    # -- epoch lifecycle ----------------------------------------------
    def _open_epoch(self, epoch: int, start: int) -> None:
        ds = self._ds
        if self._trace_parent is None:
            # stream constructed outside any span (a dataset built
            # ahead of the fit): re-capture at first pull, which runs
            # under the pipeline's stream/parse scope — so the reader
            # intervals still join the owning fit's timeline
            self._trace_parent = obs.current_span_id()
        self._plan = ds.plan(epoch)
        self._next_seq = min(start, self._plan.n_blocks)
        self._end_seq = self._plan.n_blocks
        self._buffer: dict[int, tuple] = {}
        self._next_pos = 0  # next unclaimed shard position in the plan
        self._claims: dict[int, int | None] = {}   # rid -> order pos
        self._finished: dict[int, bool] = {}       # rid exited cleanly
        self._faults: list[tuple[int, BaseException]] = []
        self._fatal: BaseException | None = None
        self._threads = []
        self._hbs = []
        self._epoch = epoch
        self._epoch_live = True
        # readers beyond the shard count would never claim work
        n = min(self._live_readers(), len(self._plan.shard_order))
        for rid in range(max(n, 1)):
            self._spawn(rid)

    # -- graftpilot live sizing (lock-free attribute reads) ------------
    def _live_readers(self) -> int:
        """The reader count this stream should run with NOW: pinned
        streams keep their construction value; live streams follow the
        graftpilot override over the env/default base."""
        ds = self._ds
        if ds._readers_pinned:
            return ds.readers
        return max(1, int(_knobs.override_or("data_readers",
                                             ds.readers)))

    def _live_window(self) -> int:
        """The reorder-window ceiling in blocks, re-read per offer —
        readers park against the LIVE value, so a widened window frees
        parked readers within one poll tick."""
        ds = self._ds
        if ds._queue_pinned:
            return ds.queue_blocks
        return max(1, int(_knobs.override_or("data_queue",
                                             ds.queue_blocks)))

    def _spawn(self, rid: int, resume_pos: int | None = None) -> None:
        ds = self._ds
        hb = _supervisor.register(
            f"data-reader:{ds.label}#e{self._epoch}r{rid}", "data")
        # host-only reader by contract (_spmd.HOST_ONLY_THREAD_NAMES):
        # it preads + decompresses shard bytes and never touches jax —
        # obs.record_span (the graftpath data.parse/data.fetch
        # intervals) is pure-stdlib span bookkeeping, unprovable to the
        # static index only because it is a cross-module call
        # graftlint: disable=thread-dispatch -- host-only shard reader: pread + zlib + stdlib span records, never device program dispatch (runtime-verified: graftsan raises on a dispatching READER_THREAD_NAME)
        t = threading.Thread(
            target=self._reader, args=(rid, hb, resume_pos),
            daemon=True, name="dask-ml-tpu-data-reader",
        )
        hb._thread = t  # registered before start: no dead-verdict race
        self._finished[rid] = False
        # a replacement reader's resumed shard IS its claim: if THIS
        # reader also dies, the next restart must replay the same
        # position — an unrecorded resume would skip the shard forever
        self._claims[rid] = resume_pos
        self._threads.append(t)
        self._hbs.append(hb)
        t.start()

    def _close_epoch(self) -> None:
        if not self._epoch_live:
            return
        with self._cond:
            self._epoch_live = False
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        for hb in self._hbs:
            hb.retire()
        self._threads, self._hbs = [], []
        self._buffer = {}

    # -- reader side (host-only threads) ------------------------------
    def _claim(self, rid: int) -> int | None:
        with self._cond:
            if not self._epoch_live:
                return None
            if self._next_pos >= len(self._plan.shard_order):
                return None
            p = self._next_pos
            self._next_pos += 1
            self._claims[rid] = p
            return p

    def _offer(self, seq: int, block) -> bool:
        """Park until ``seq`` fits the window, then buffer it.  Returns
        False when the stream closed.  Replayed sequence numbers that
        were already delivered (or already buffered) are dropped — the
        exactly-once half of reader replay."""
        with self._cond:
            while self._epoch_live and \
                    seq >= self._next_seq + self._live_window():
                self._cond.wait(timeout=_POLL_S)
            if not self._epoch_live:
                return False
            if seq >= self._next_seq and seq not in self._buffer:
                self._buffer[seq] = block
                self._cond.notify_all()
            return True

    def _reader(self, rid: int, hb, resume_pos: int | None) -> None:
        ds = self._ds
        try:
            pos = resume_pos
            while True:
                if pos is None:
                    pos = self._claim(rid)
                if pos is None:
                    break
                shard = self._plan.shard_order[pos]
                order = self._plan.block_orders[shard]
                base = self._plan.starts[pos]
                reader = ds.manifest.open_shard(shard)
                try:
                    for j in range(len(order)):
                        seq = base + j
                        if seq < self._next_seq and \
                                seq not in self._buffer:
                            # resumed stream prefix / already-delivered
                            # replay range: nothing to read
                            continue
                        if not self._epoch_live:
                            return
                        _maybe_fault("data-reader")
                        hb.beat()
                        if ds.fetch_latency_s:
                            # the emulated remote-store GET is a FETCH
                            # interval, distinct from parse CPU — the
                            # critical-path engine attributes them to
                            # different categories (fetch-bound vs
                            # parse-bound are different fixes)
                            t_f = time.perf_counter()
                            time.sleep(ds.fetch_latency_s)
                            obs.record_span(
                                "data.fetch", t_f, time.perf_counter(),
                                parent=self._trace_parent, seq=seq)
                        t_p = time.perf_counter()
                        block = reader.read_block(int(order[j]))
                        obs.record_span(
                            "data.parse", t_p, time.perf_counter(),
                            parent=self._trace_parent, shard=shard,
                            seq=seq)
                        if not self._offer(seq, block):
                            return
                finally:
                    reader.close()
                with self._cond:
                    self._claims[rid] = None
                pos = None
            with self._cond:
                self._finished[rid] = True
                self._cond.notify_all()
        except _ThreadCrash:
            return  # simulated hard death: vanish without reporting —
            #         the consumer's liveness poll must catch this
        except BaseException as exc:
            with self._cond:
                self._faults.append((rid, exc))
                self._cond.notify_all()

    # -- consumer side -------------------------------------------------
    def _restart_reader(self, rid: int, error: str) -> None:
        """The budgeted-restart verdict: death books, budget gate,
        replacement reader replaying the in-flight shard range."""
        ds = self._ds
        hb = self._hbs[rid] if rid < len(self._hbs) else None
        name = hb.name if hb is not None else f"data-reader#{rid}"
        _supervisor.note_death("data", name, error=error)
        obs.event("data.reader_fault", label=ds.label, reader=rid,
                  epoch=self._epoch, error=error)
        if self._restarts >= ds.reader_restarts or \
                not self._budget.acquire("data-reader"):
            raise BudgetExhausted(
                f"dataset {ds.label!r}: reader restart budget exhausted "
                f"after {self._restarts} restart(s): {error}")
        self._restarts += 1
        _registry().counter("data.reader_restart", ds.label).inc()
        resume = self._claims.get(rid)
        new_rid = len(self._threads)
        self._spawn(new_rid, resume_pos=resume)
        self._claims[rid] = None
        self._finished[rid] = True  # the dead unit is replaced
        _supervisor.note_restart("data", name)

    def _check_readers(self) -> None:
        """Handle reported faults and silently-dead readers (run on the
        consumer thread, outside the condition lock)."""
        with self._cond:
            faults = list(self._faults)
            self._faults = []
        for rid, exc in faults:
            if isinstance(exc, BudgetExhausted):
                raise exc
            self._restart_reader(rid, f"{type(exc).__name__}: {exc}")
        for rid, t in enumerate(list(self._threads)):
            if not t.is_alive() and not self._finished.get(rid, False):
                with self._cond:
                    if self._faults:
                        continue  # a report landed after the poll; next pass
                self._restart_reader(
                    rid, "data reader died without reporting")
        # graftpilot mid-epoch scale-UP: the live readers knob rose and
        # unclaimed shards remain — spawn the difference (each new
        # reader claims from the shared cursor like any other).  Scale-
        # DOWN is lazy: surplus readers drain their claimed shard and
        # exit at the next claim.  Runs on the consumer thread outside
        # the condition (the _spawn/_restart_reader idiom: supervisor
        # registration must not nest under data.readers).
        live = self._live_readers()
        with self._cond:
            if not self._epoch_live:
                return
            unclaimed = len(self._plan.shard_order) - self._next_pos
            active = sum(
                1 for rid, t in enumerate(self._threads)
                if t.is_alive() and not self._finished.get(rid, False))
            spawn = min(live - active, unclaimed)
        for _ in range(max(spawn, 0)):
            _registry().counter("data.reader_scale",
                                self._ds.label).inc()
            self._spawn(len(self._threads))

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        while True:
            if not self._epoch_live:
                if not self._epochs:
                    self.close()
                    raise StopIteration
                epoch = self._epochs.pop(0)
                start, self._first_start = self._first_start, 0
                self._open_epoch(epoch, start)
            block = self._await_block()
            if block is not None:
                return block
            self._close_epoch()  # epoch drained; loop to the next

    def _await_block(self):
        """The next in-order block of the live epoch, or None when the
        epoch is drained.  A contiguous wait for the head-of-line block
        is the data plane's reorder-queue wait: it lands in the
        ``data.queue_wait_s`` histogram (scraped via ``/metrics``) and
        as ONE ``data.queue_wait`` span for the critical-path engine —
        which attributes it to the readers' concurrent ``data.parse``
        work when that explains it (design.md §19)."""
        ds = self._ds
        wait_t0 = None
        while True:
            with self._cond:
                if self._next_seq >= self._end_seq:
                    return None
                block = self._buffer.pop(self._next_seq, None)
                if block is not None:
                    self._next_seq += 1
                    self._cond.notify_all()  # slide the window
                else:
                    if wait_t0 is None:
                        wait_t0 = time.perf_counter()
                    self._cond.wait(timeout=_POLL_S)
            if block is None:
                self._check_readers()  # liveness poll (outside the lock)
                continue
            if wait_t0 is not None:
                now = time.perf_counter()
                _registry().histogram(
                    "data.queue_wait_s", ds.label).record(now - wait_t0)
                obs.record_span("data.queue_wait", wait_t0, now,
                                seq=self._next_seq - 1)
            self.blocks_delivered += 1
            rows = int(np.shape(block[0])[0]) if len(block) else 0
            self.rows_delivered += rows
            reg = _registry()
            reg.counter("data.blocks", ds.label).inc()
            reg.counter("data.rows", ds.label).inc(rows)
            if len(block) == 1:
                return block[0], None
            if len(block) == 2:
                return block[0], block[1]
            return block

    def close(self) -> None:
        """Stop the readers and drop buffered blocks.  Idempotent —
        the pipeline's source-close hook and ``with`` both land here."""
        if self._closed:
            return
        self._closed = True
        self._close_epoch()
        self._epochs = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
