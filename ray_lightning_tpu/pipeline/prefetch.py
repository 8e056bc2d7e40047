"""Device prefetch pipeline: overlap host batch work with device compute.

The trainer's hot loop used to be strictly serial per step: assemble the
batch on the host (index/copy/cast), ``device_put`` it (sharded,
multi-process aware), THEN dispatch the jitted step. jax dispatch is
async, so the device finishes the previous step while the host sits in
numpy — but the *next* step cannot dispatch until its input exists on
device, and at production batch sizes the host work is milliseconds the
dispatch queue spends empty ("Exploring the limits of Concurrency in ML
Training on Google TPUs": the win is keeping that queue non-empty).

`DevicePrefetcher` is the classic bounded double/N-buffer stage: a
single background thread pulls host batches from the iterator, runs the
caller's ``place_fn`` (cast + shard — `Strategy.shard_batch` or the
trainer's accumulation split; `jax.device_put` and
`make_array_from_process_local_data` are both thread-safe and issue only
local work), and parks up to ``depth`` device-resident batches in a
bounded queue. The consumer's ``next()`` then usually returns a batch
whose transfer was issued one step ago.

Contracts the trainer relies on:

  * ORDER: batches come out exactly in iterator order (single producer,
    FIFO queue) — bitwise-identical training vs the synchronous path.
  * BACKPRESSURE: at most ``depth`` placed batches + 1 in the producer's
    hands exist at any time; slow consumers never accumulate device
    memory. ``depth`` buffers of HBM is the deliberate, bounded cost.
  * SHUTDOWN: ``close()`` (or exiting the context / exhausting the
    iterator) unblocks and joins the producer thread — a mid-epoch
    ``break`` (max_steps, early stop, preemption drain) must not leak a
    thread holding the loader. Idempotent.
  * ERRORS: a producer-side exception (bad batch, loader bug) is
    re-raised at the consumer's ``next()``, not swallowed in a thread.
  * METRICS: `stats` counts how often the consumer found a batch already
    waiting (`occupancy`) and how long it blocked (`wait_s`) — the
    pipeline-health numbers surfaced through ``callback_metrics``.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Iterator, Optional

from ray_lightning_tpu.telemetry.spans import (
    NULL_RECORDER,
    PH_DATA_WAIT,
    PH_H2D,
    THREAD_PRODUCER,
    annotate,
)


@dataclass
class PrefetchStats:
    """Occupancy accounting for one prefetcher's lifetime."""

    batches: int = 0      # batches handed to the consumer
    hits: int = 0         # ...that were already buffered (no wait)
    wait_s: float = 0.0   # total consumer time blocked on the queue
    put_wait_s: float = 0.0  # total producer time blocked (backpressure)
    _depth: int = field(default=0, repr=False)

    @property
    def occupancy(self) -> float:
        """Fraction of batches served without blocking — 1.0 means the
        device never waited for the host; 0.0 means no overlap at all
        (the synchronous behavior this pipeline exists to beat)."""
        return self.hits / self.batches if self.batches else 0.0

    def to_metrics(self) -> dict:
        return {
            "prefetch_batches": float(self.batches),
            "prefetch_occupancy": self.occupancy,
            "prefetch_wait_s": self.wait_s,
            "prefetch_depth": float(self._depth),
        }


class _Stop:
    """Queue sentinel: normal end of the source iterator."""


class _Raise:
    """Queue sentinel carrying a producer-side exception."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class DevicePrefetcher(Iterable[Any]):
    """Iterate ``source`` with ``place_fn`` applied N batches ahead.

    ``place_fn`` maps one host batch to its device-resident form; it runs
    on the producer thread. ``depth`` >= 1 is the buffer bound (2 — the
    classic double buffer — hides one full host latency per step and is
    the default the trainer uses).
    """

    def __init__(self, source: Iterable[Any],
                 place_fn: Callable[[Any], Any],
                 depth: int = 2, name: str = "rlt-prefetch",
                 recorder: Any = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = depth
        #: telemetry span recorder (telemetry/spans.py): H2D placement
        #: spans from the producer thread (overlapped with compute —
        #: thread-tagged so goodput never double-charges them) and
        #: data-wait spans when the consumer actually blocked
        self._recorder = recorder or NULL_RECORDER
        self.stats = PrefetchStats(_depth=depth)
        self._source = iter(source)
        self._place = place_fn
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, name=name, daemon=True)
        self._thread.start()
        self._closed = False

    # ---- producer --------------------------------------------------------

    def _produce(self) -> None:
        try:
            for batch, item in enumerate(self._source):
                if self._stop.is_set():
                    return
                # `batch` (this stream's index of the batch being placed)
                # joins the producer's span to the step that consumes it
                with self._recorder.span(PH_H2D, thread=THREAD_PRODUCER,
                                         meta={"batch": batch}):
                    placed = self._place(item)
                # bounded put with a timeout poll so close() can always
                # unblock the producer even if the consumer vanished
                # without draining
                t0 = time.perf_counter()
                while not self._stop.is_set():
                    try:
                        self._q.put(placed, timeout=0.05)
                        break
                    except queue.Full:
                        continue
                self.stats.put_wait_s += time.perf_counter() - t0
            self._final_put(_Stop())
        except BaseException as exc:  # noqa: BLE001 — carried to consumer
            self._final_put(_Raise(exc))

    def _final_put(self, sentinel: Any) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(sentinel, timeout=0.05)
                return
            except queue.Full:
                continue

    # ---- consumer --------------------------------------------------------

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self._closed:
            raise StopIteration
        hit = not self._q.empty()
        # the wait is on the profiler's clock hit or miss (`rlt.data_wait`);
        # only a miss enters the ring below
        with annotate(PH_DATA_WAIT):
            t0 = time.perf_counter()
            item = self._q.get()
            waited = time.perf_counter() - t0
        if isinstance(item, _Stop):
            self.close()
            raise StopIteration
        if isinstance(item, _Raise):
            self.close()
            raise item.exc
        self.stats.batches += 1
        if hit:
            self.stats.hits += 1
        else:
            self.stats.wait_s += waited
            # a miss is real main-thread data-wait: the device's input
            # was not resident when the loop asked — the timeline span
            # that explains a goodput data_wait bucket
            self._recorder.record(PH_DATA_WAIT, t0, waited)
        return item

    # ---- lifecycle -------------------------------------------------------

    def close(self) -> None:
        """Stop the producer and join it. Safe to call repeatedly and
        from ``finally`` blocks; buffered batches are dropped (they are
        just device arrays — the GC reclaims them)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # drain so a producer blocked in put() sees the stop flag promptly
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            # a place_fn wedged >5s (e.g. a multi-host device_put against
            # a dead peer): don't hang the trainer's exit path on it, but
            # never let the leak be invisible either
            import logging

            logging.getLogger(__name__).warning(
                "prefetch producer %r still alive after close(); a "
                "placement call is wedged — the thread is daemon and "
                "will not block process exit", self._thread.name)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.close()
        return None


def prefetch_to_device(source: Iterable[Any],
                       place_fn: Callable[[Any], Any],
                       depth: int = 2,
                       recorder: Any = None) -> Iterable[Any]:
    """Functional form: ``depth <= 0`` returns the synchronous pipeline
    (place inline, no thread) so call sites can switch with one knob.
    ``recorder`` (telemetry/spans.py) tags H2D/data-wait spans; in the
    synchronous path the placement blocks the main thread, so its span
    is main-thread (timeline-visible, deliberately outside the goodput
    stall buckets — it is the cost the prefetcher exists to hide)."""
    if depth <= 0:
        rec = recorder or NULL_RECORDER
        def _sync():
            for item in source:
                with rec.span(PH_H2D):
                    placed = place_fn(item)
                yield placed
        return _sync()
    return DevicePrefetcher(source, place_fn, depth=depth,
                            recorder=recorder)
