"""DevicePrefetcher: double-buffered host->HBM transfer, as a public API.

Extracted from ``parallel/trainer.py`` so the streaming input pipeline's
terminal ``Dataset.to_device_iterator()`` and ``DistributedTrainer.fit``
share ONE prefetch implementation (``parallel.trainer`` keeps a
back-compat re-export). This module deliberately imports no jax and no
trainer code: the device commit is the injected ``put`` callable, so the
prefetcher composes with any dispatch layer (``trainer.put_batch``, a
plain ``jax.device_put``, or an identity function in host-only tests).

Telemetry at this boundary (``docs/OBSERVABILITY.md``): the counters
``input.batches_put`` / ``input.bytes_put`` always count; with the hot-span
gate on (``spans.hot_spans``, resolved once per prefetcher) every batch
leaves ``input:produce`` (producer thread: the pull from the host
iterator), ``input:wait`` (consumer: the queue get) and ``input:put``
(consumer: the device commit), all carrying the batch's ordinal.
"""
from __future__ import annotations

import contextvars
import queue
import threading
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.observability import spans as obsspans
from mmlspark_tpu.reliability import watchdog as _watchdog
from mmlspark_tpu.utils import config as mmlconfig


def _nbytes(tree: Any) -> int:
    """Bytes of a host batch: the sum of its leaves' ``nbytes`` (dicts,
    lists and tuples are walked; this module imports no jax)."""
    if isinstance(tree, dict):
        return sum(_nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(_nbytes(v) for v in tree)
    return int(getattr(tree, "nbytes", 0))


class DevicePrefetcher:
    """Double-buffered host->HBM prefetch (SURVEY.md §7 "streaming host→HBM
    without stalls").

    A background thread pulls host batches — the expensive host work: epoch
    shuffling, tail padding, feature assembly — and queues them ``depth``
    deep. The consuming ``next()`` commits each batch's ``device_put`` on the
    caller's thread and returns immediately: JAX dispatch is asynchronous, so
    the transfer overlaps the still-running previous step and the Python loop
    stays ahead of the device. All JAX runtime calls therefore happen on ONE
    thread — issuing ``device_put`` from the producer thread concurrently
    with a jitted execution aborts flakily inside the multi-device CPU
    runtime (XLA client race), and single-threaded dispatch loses nothing
    because the runtime pipelines the async transfers anyway.
    Exceptions in the producer re-raise at the consuming ``next()``.
    """

    _SENTINEL = object()

    def __init__(self, host_batches: Iterable[Dict[str, Any]],
                 put: Callable[[Dict[str, Any]], Any],
                 depth: Optional[int] = None):
        self.depth = depth if depth is not None else int(
            mmlconfig.get("runtime.prefetch_depth"))
        self._put = put
        self._q: queue.Queue = queue.Queue(maxsize=max(self.depth, 1))
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._done = False
        self._closed = False
        self._telemetry = obsmetrics.metrics_enabled()
        # per-batch spans and counts: the gate (None, or the span
        # constructor) and the counters are resolved once, here
        self._hot = hot = obsspans.hot_spans()
        self._batches_put = obsmetrics.counter("input.batches_put")
        self._bytes_put = obsmetrics.counter("input.bytes_put")
        self._batch = 0  # ordinal of the next batch handed to ``put``

        def pulled():
            """``host_batches``, each pull inside ``input:produce``: the
            host work of one batch (shuffle, decode, assembly), apart
            from the back-pressure wait on the queue below."""
            it = iter(host_batches)
            n = 0
            while True:
                with hot("input", "produce", batch=n) as sp:
                    try:
                        hb = next(it)
                    except StopIteration:
                        sp.drop()
                        return
                yield hb
                n += 1

        def run():
            # liveness: beats on every produced batch AND on every bounded
            # wait tick — a producer parked on a full queue is healthy
            # (back-pressure), one wedged inside next(host_batches) is the
            # stall the watchdog should catch
            beat = _watchdog.register("data.prefetch")
            try:
                for hb in (pulled() if hot else host_batches):
                    beat.beat()
                    if self._stop.is_set():
                        return
                    # bounded put that notices close(): never blocks forever
                    while not self._stop.is_set():
                        try:
                            self._q.put(hb, timeout=0.1)
                            break
                        except queue.Full:
                            beat.beat()
                            continue
            except BaseException as e:  # surfaced on the consumer side
                self._err = e
            finally:
                beat.close()
                # bounded sentinel put: a full queue must not lose the
                # end-of-stream marker, but close() must still unblock us
                while not self._stop.is_set():
                    try:
                        self._q.put(self._SENTINEL, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        # the producer inherits the caller's context, so its spans nest
        # under the span this prefetcher was built in (``trainer:fit``)
        self._thread = threading.Thread(
            target=contextvars.copy_context().run, args=(run,), daemon=True,
            name="mmlspark-tpu-prefetch")
        self._thread.start()

    def close(self) -> None:
        """Stop the producer and drop queued host batches. Call from a
        ``finally`` when abandoning the stream early. Idempotent: a second
        call (or a call after the producer already exited) is a no-op —
        the ``TrainCheckpointer.close()`` contract."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # join FIRST (the producer's bounded put notices _stop within 0.1s),
        # then drain — draining before the join can free a slot that the
        # producer immediately refills, keeping a batch buffered
        self._thread.join(timeout=5)
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._done = True

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self._done:
            raise StopIteration
        n = self._batch
        hot = self._hot
        if hot:
            with hot("input", "wait", batch=n) as sp:
                item = self._q.get()
                if item is self._SENTINEL:
                    sp.drop()  # the end of the stream is no batch
        else:
            item = self._q.get()
        if self._telemetry:
            obsmetrics.gauge("data.prefetch_queue_depth").set(
                self._q.qsize())
        if item is self._SENTINEL:
            self._done = True
            self._thread.join()
            if self._err is not None:
                raise self._err
            raise StopIteration
        nbytes = _nbytes(item)
        self._batch = n + 1
        self._batches_put.inc()
        self._bytes_put.inc(nbytes)
        if hot:
            with hot("input", "put", batch=n, bytes=nbytes):
                return self._put(item)
        return self._put(item)
