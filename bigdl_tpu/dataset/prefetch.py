"""Asynchronous input pipeline: threaded prefetch + host->device staging.

Reference: the reference hides input cost behind compute with
`MTLabeledBGRImgToBatch` (dataset/image/MTLabeledBGRImgToBatch.scala), a
multi-threaded batcher whose worker pool stays ahead of the synchronous
SGD loop (BigDL paper, arXiv:1804.05839 §3); the MLPerf TPU-pod work
(arXiv:1909.09756) identifies exactly this overlap as the first-order
lever for keeping accelerator utilization up at scale.

TPU-native re-design: the device step is dispatched asynchronously, so
the only thing serializing input against compute is the HOST — the
transformer chain (decode, augment, numpy collation) running on the main
thread between steps.  :class:`PrefetchIterator` moves that chain onto a
background worker thread feeding a bounded queue (depth
``BIGDL_TPU_PREFETCH_DEPTH``, default 2), and optionally runs a staging
callable in the worker too — the Optimizer stages the *next* batch onto
devices (`_put_batch` under the training sharding) while the current
step executes, true host->device double-buffering.

Robustness contracts preserved (the whole point of running the chain in
ONE worker, not a pool):

- deterministic order: items come out exactly as the source yields them,
  and any per-item RNG (augmentation draws, chaos counters) advances in
  the same sequence as the synchronous path;
- typed exceptions (``CorruptRecord``, chaos ``fail@`` schedules, a
  supervisor ``StallError`` async-raised into the worker) are captured
  at the item position where they occurred and re-raised at the
  consumer's ``next()`` — the optimizer's retry loop and the skip-budget
  machinery see them unchanged;
- supervisor liveness: the worker heartbeats its own supervision channel
  (``Supervisor.channel``), so a stalled transformer chain trips the
  ``data`` deadline even while the main thread is busy in a step, and a
  worker parked on a FULL queue (consumer-paced — healthy) keeps
  refreshing its beat instead of false-tripping;
- clean shutdown: ``close()`` signals the worker, joins it, and closes
  the source generator — no leaked threads across a ``StallError`` retry
  re-entry (same discipline as ``Engine._discover_devices``).

:class:`ThreadedShardReader` is the pure-Python fallback for the native
shard prefetcher (csrc/prefetch.cc): N reader threads interleave whole
shards into one bounded queue when the .so is absent or predates the
``bigdl_prefetch_*`` symbols — instead of silently degrading to
sequential reads.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

from ..utils import config, telemetry

logger = logging.getLogger("bigdl_tpu")

__all__ = ["PrefetchIterator", "ThreadedShardReader", "prefetch_depth"]

# queue item tags: (kind, payload)
_ITEM, _ERR, _DONE = 0, 1, 2


def prefetch_depth(default: int = 2) -> int:
    """The ``BIGDL_TPU_PREFETCH_DEPTH`` knob, read at pipeline
    construction (per epoch / per eval pass, so tests can flip it between
    runs).  0 disables prefetching entirely — the synchronous path."""
    return max(0, config.get_int("PREFETCH_DEPTH", default))


class PrefetchIterator:
    """Bounded-depth background prefetcher over any iterator.

    One worker thread runs ``pre_fire()`` (a chaos hook), pulls
    ``next(source)`` and applies ``transform`` per item, then parks the
    result in a queue of at most ``depth`` ready items.  The consumer
    iterates as usual; ``queue_depth()`` exposes how many items were
    ready at call time (the straggler detector's pipeline-vs-consumer
    signal).

    ``supervisor`` (a ``utils.supervisor.Supervisor``) gets a dedicated
    heartbeat channel beaten from the worker under the ``data`` phase.
    """

    def __init__(self, source, depth: Optional[int] = None,
                 transform: Optional[Callable] = None,
                 pre_fire: Optional[Callable[[], None]] = None,
                 supervisor=None, phase: str = "data",
                 name: str = "bigdl-prefetch"):
        self._source = iter(source)
        self.depth = prefetch_depth() if depth is None else max(1, int(depth))
        self._transform = transform
        self._pre_fire = pre_fire
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._finished = False
        self._phase = phase
        self._chan = (supervisor.channel(name, phase=phase)
                      if supervisor is not None else None)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    # -- worker ---------------------------------------------------------

    def _beat(self) -> None:
        if self._chan is not None:
            self._chan.beat(self._phase)

    def _run(self) -> None:
        kind, payload = _DONE, None
        # telemetry: the worker owns its own named thread track — per-item
        # produce spans land there, separate from the consumer's data_wait
        telemetry.thread_name(self._thread.name)
        try:
            while not self._stop.is_set():
                self._beat()
                if self._pre_fire is not None:
                    self._pre_fire()
                t0 = time.perf_counter()
                # the chain alone; what `transform` adds (the Optimizer's
                # `prefetch.stage`) reads apart inside `prefetch.item`
                with telemetry.span("prefetch.produce") as produce_span:
                    try:
                        item = next(self._source)
                    except StopIteration:
                        produce_span.drop()
                        break
                if self._transform is not None:
                    item = self._transform(item)
                telemetry.complete("prefetch.item",
                                   time.perf_counter() - t0)
                if not self._put((_ITEM, item)):
                    return  # consumer closed while the queue was full
        except BaseException as e:  # noqa: BLE001 — forwarded, including a
            # supervisor StallError async-raised into THIS thread
            kind, payload = _ERR, e
        finally:
            self._put((kind, payload))
            if self._chan is not None:
                self._chan.close()

    def _put(self, item) -> bool:
        """Bounded put that stays responsive to close().  A worker parked
        on a FULL queue is consumer-paced (healthy), so each wait slice
        refreshes the heartbeat — only a worker stuck producing (decode,
        augment, a chaos stall) goes silent and trips the deadline."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                self._beat()
        return False

    # -- consumer -------------------------------------------------------

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        while True:
            try:
                kind, payload = self._q.get(timeout=1.0)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # the worker always parks a sentinel in its finally;
                    # dead-with-empty-queue means even that failed
                    self._finished = True
                    raise RuntimeError(
                        "prefetch worker exited without a result")
        if kind == _ITEM:
            return payload
        self._finished = True
        if kind == _ERR:
            raise payload
        raise StopIteration

    def queue_depth(self) -> int:
        """Ready items right now (approximate, like Queue.qsize).  A
        non-empty queue at fetch time means the pipeline outpaced the
        consumer — the consumer, not the input, set the iteration pace."""
        return self._q.qsize()

    # -- lifecycle ------------------------------------------------------

    def close(self) -> None:
        """Stop the worker and join it; safe to call repeatedly.  Runs the
        abandoned source generator's finalizers (quarantine accounting in
        StreamingRecordDataSet.data lives in a ``finally``)."""
        self._stop.set()
        # a worker blocked on put observes the stop within its 50ms slice;
        # drain anything parked so close never deadlocks on a full queue
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():  # pragma: no cover — wedged in C
            logger.warning("prefetch worker did not exit within 10s "
                           "(wedged in a native call?)")
        close = getattr(self._source, "close", None)
        if close is not None:
            try:
                close()
            except Exception:  # noqa: BLE001 — finalization is best-effort
                logger.exception("prefetch source close failed (non-fatal)")
        if self._chan is not None:
            self._chan.close()
        self._finished = True

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class ThreadedShardReader:
    """Pure-Python multi-threaded shard reader: N threads each stream
    whole shards (``read_fn(path)`` -> record iterator) into one bounded
    queue — the fallback for the native prefetcher (csrc/prefetch.cc)
    when the library is absent or predates the ``bigdl_prefetch_*``
    symbols.  Same contract as the native reader: record order
    interleaves across shards, per-shard order is preserved, and the
    first reader error is re-raised at the consumer."""

    def __init__(self, paths: Iterable[str], num_threads: int,
                 read_fn: Callable[[str], Iterator], capacity: int = 256):
        self._paths = list(paths)
        self._read_fn = read_fn
        self._q: queue.Queue = queue.Queue(maxsize=max(2, capacity))
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._next = 0
        self._finished = False
        self._errored = False
        n = max(1, min(int(num_threads), max(len(self._paths), 1)))
        self._active = n
        self._threads = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"bigdl-shard-reader-{i}")
            for i in range(n)]
        for t in self._threads:
            t.start()

    def _take_path(self) -> Optional[str]:
        with self._lock:
            if self._next >= len(self._paths):
                return None
            p = self._paths[self._next]
            self._next += 1
            return p

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                path = self._take_path()
                if path is None:
                    break
                for rec in self._read_fn(path):
                    if not self._put((_ITEM, rec)):
                        return
        except BaseException as e:  # noqa: BLE001 — forwarded to consumer
            # one rotten shard ends the whole pass, like the sequential
            # reader raising mid-iteration: queue the error BEHIND the
            # records already read (the consumer drains up to it), then
            # stop the sibling readers
            self._errored = True
            self._put((_ERR, e))
            self._stop.set()
            return
        finally:
            with self._lock:
                self._active -= 1
                last = self._active == 0
            if last and not self._errored:
                self._put((_DONE, None))

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self) -> "ThreadedShardReader":
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        while True:
            try:
                kind, payload = self._q.get(timeout=1.0)
                break
            except queue.Empty:
                if not any(t.is_alive() for t in self._threads):
                    self._finished = True
                    raise RuntimeError(
                        "shard reader threads exited without a result")
        if kind == _ITEM:
            return payload
        self._finished = True
        if kind == _ERR:
            raise payload
        raise StopIteration

    def close(self) -> None:
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        for t in self._threads:
            t.join(timeout=10.0)
        self._finished = True

    def __enter__(self) -> "ThreadedShardReader":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
