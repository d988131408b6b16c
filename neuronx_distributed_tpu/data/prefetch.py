"""Device-prefetch input pipeline: stage batch N+1..N+depth onto the
accelerator while step N runs.

The reference framework gets input/compute overlap from torch-xla's
``MpDeviceLoader``/``ParallelLoader`` (a background thread feeding per-device
queues, SURVEY §L1); our ``fit()`` loop previously handed the jitted step a
*host* batch every iteration, so the step's first act on a real TPU was a
blocking host→device copy.  :class:`DevicePrefetcher` closes that gap
TPU-natively:

- a bounded background thread pulls from any step-indexed ``data(step)``
  callable (or an iterator adapter) and ``jax.device_put``'s each batch
  against the step's batch shardings — double/triple buffering is just
  ``depth=2``/``3``;
- delivery is **step-indexed and rewindable**: ``get(step)`` hands back the
  staged batch for exactly that step, and a non-sequential request (a
  resilience policy rolling the run back to an earlier step) flushes the
  staged pipeline and restarts staging at the requested step — exact-resume
  and rollback semantics are preserved, never approximated;
- queue-depth / staged-ahead gauges and rewind / staged counters land in the
  obs registry so the overlap is observable, not assumed;
- ``close()`` (or the context manager) drains the worker deterministically:
  no leaked thread, no stale staged batch — ``fit()`` closes it on every
  exit path including early stop and SIGTERM checkpointing.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Optional

import jax

from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# metric names (the obs.schemas.REGISTRY_METRICS contract)
QUEUE_DEPTH = "data/prefetch_queue_depth"
STAGED_AHEAD = "data/prefetch_staged_ahead"
REWINDS_TOTAL = "data/prefetch_rewinds_total"
STAGED_TOTAL = "data/prefetch_batches_staged_total"
WAIT_MS = "data/prefetch_wait_ms"

_POLL_S = 0.05  # worker put/consumer get poll so close()/rewind never hang


class DevicePrefetcher:
    """Bounded background staging of ``data(step)`` batches onto devices.

    Args:
      source: ``source(step) -> host batch`` (step-indexed, the rewindable
        form ``fit`` prefers) or any iterable of batches (adapted; iterators
        deliver in order and cannot rewind).
      depth: staged-ahead bound (2 = double buffering, 3 = triple, ...).
      shardings: a pytree of ``jax.sharding.Sharding`` (or one sharding
        broadcast over the batch tree) for the staged ``device_put`` — pass
        the step's batch shardings so staged batches land exactly where the
        jitted step wants them; ``None`` stages to the default device.
      registry: an ``obs.MetricRegistry`` for the gauges/counters (optional).
      name: metric/thread-name prefix (default ``data``).

    ``get(step)`` blocks until that step's batch is staged (the wait is the
    pipeline's *observed* stall, exported as ``data/prefetch_wait_ms``).
    Exceptions from ``source`` (including ``StopIteration`` from an
    exhausted iterator) surface on the ``get`` that would have consumed the
    failing step."""

    def __init__(
        self,
        source: "Callable[[int], Any] | Iterable[Any]",
        *,
        depth: int = 2,
        shardings: Any = None,
        registry: Any = None,
        name: str = "data",
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if callable(source):
            self._source = source
            self._rewindable = True
        else:
            it = iter(source)
            self._source = lambda step: next(it)
            self._rewindable = False
        self.depth = int(depth)
        self._shardings = shardings
        self._registry = registry
        self._name = name
        self._queue: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._lock = threading.Lock()
        self._gen = 0            # staging generation; a rewind bumps it
        self._thread: Optional[threading.Thread] = None
        self._next_out: Optional[int] = None  # step the consumer gets next
        self._staged_to = 0      # worker progress (gauge only)
        self._closed = False
        self.rewinds = 0
        if registry is not None:
            from neuronx_distributed_tpu.obs import MS_BUCKETS

            self._ms_buckets = MS_BUCKETS
            registry.gauge(QUEUE_DEPTH)
            registry.gauge(STAGED_AHEAD)
            registry.counter(REWINDS_TOTAL)
            registry.counter(STAGED_TOTAL)
            registry.histogram(WAIT_MS, MS_BUCKETS)

    # -- worker ------------------------------------------------------------

    def _stale(self, gen: int) -> bool:
        with self._lock:
            return self._closed or gen != self._gen

    def _offer(self, gen: int, item: tuple) -> bool:
        """Blocking put that abandons the item when the generation went
        stale (rewind/close) instead of wedging on a full queue."""
        while True:
            if self._stale(gen):
                return False
            try:
                self._queue.put(item, timeout=_POLL_S)
                return True
            except queue.Full:
                continue

    def _worker(self, gen: int, start: int) -> None:
        step = start
        while not self._stale(gen):
            try:
                batch = self._source(step)
                staged = (jax.device_put(batch) if self._shardings is None
                          else jax.device_put(batch, self._shardings))
            except BaseException as e:  # delivered to the consumer's get()
                self._offer(gen, (gen, step, None, e))
                return
            if not self._offer(gen, (gen, step, staged, None)):
                return
            with self._lock:
                self._staged_to = step + 1
            if self._registry is not None:
                self._registry.counter(STAGED_TOTAL).inc()
            step += 1

    # -- consumer ----------------------------------------------------------

    def _restart(self, step: int) -> None:
        """(Re)start staging at ``step``: bump the generation (the old
        worker sees it and exits), drop staged batches, spawn a worker."""
        old = self._thread
        with self._lock:
            was_running = old is not None
            self._gen += 1
            gen = self._gen
            self._next_out = step
            self._staged_to = step
        self._drain()
        if was_running:
            # the retired worker sees the generation and exits (a put it was
            # blocked in has room now): wait for it, so that two workers
            # never call the source at once and close() — which joins only
            # the newest — leaves no thread behind
            self._join(old)
            self._drain()  # what it put on its way out
            with self._lock:
                self._staged_to = step
            self.rewinds += 1
            if self._registry is not None:
                self._registry.counter(REWINDS_TOTAL).inc()
            logger.info("prefetch[%s]: rewound staging to step %d",
                        self._name, step)
        self._thread = threading.Thread(
            target=self._worker, args=(gen, step),
            name=f"{self._name}-prefetch", daemon=True)
        self._thread.start()

    def _join(self, worker: threading.Thread, timeout: float = 5.0) -> None:
        worker.join(timeout=timeout)
        if worker.is_alive():  # pragma: no cover - source wedged in user code
            logger.warning("prefetch[%s]: worker did not stop in %.1fs",
                           self._name, timeout)

    def _drain(self) -> None:
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                return

    def get(self, step: int) -> Any:
        """The staged batch for exactly ``step``.  Sequential calls stream
        from the staged pipeline; a non-sequential step (policy rollback,
        or the very first call fixing the start step) rewinds/starts
        staging there."""
        if self._closed:
            raise RuntimeError(f"prefetch[{self._name}] is closed")
        if self._thread is None or step != self._next_out:
            if self._thread is not None and not self._rewindable:
                raise RuntimeError(
                    f"prefetch[{self._name}]: cannot rewind to step {step} "
                    f"(expected {self._next_out}): the source is an "
                    "iterator — rewinds need step-indexed data(step)")
            self._restart(step)
        import time as _time

        t0 = _time.perf_counter()
        while True:
            try:
                gen, s, staged, err = self._queue.get(timeout=_POLL_S)
            except queue.Empty:
                if self._thread is not None and not self._thread.is_alive() \
                        and self._queue.empty():
                    raise RuntimeError(
                        f"prefetch[{self._name}]: worker died without "
                        f"delivering step {step}")
                continue
            if gen != self._gen:
                continue  # staged before a rewind: stale, drop
            break
        wait_s = _time.perf_counter() - t0
        if err is not None:
            raise err
        assert s == step, f"prefetch ordering bug: got {s}, wanted {step}"
        self._next_out = step + 1
        if self._registry is not None:
            self._registry.gauge(QUEUE_DEPTH).set(self._queue.qsize())
            with self._lock:
                ahead = self._staged_to - (step + 1)
            self._registry.gauge(STAGED_AHEAD).set(max(ahead, 0))
            self._registry.histogram(WAIT_MS, self._ms_buckets).observe(
                wait_s * 1e3)
        return staged

    def close(self, timeout: float = 5.0) -> None:
        """Stop staging and join the worker.  Idempotent; after close the
        queue holds nothing (no stale staged batch can leak into a resumed
        run) and the thread is gone (asserted by the drain smoke tests)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._gen += 1
        self._drain()  # unblock a worker stuck in put
        if self._thread is not None:
            self._join(self._thread, timeout)
            self._thread = None
        self._drain()  # whatever the worker put while we were joining
        if self._registry is not None:
            self._registry.gauge(QUEUE_DEPTH).set(0)
            self._registry.gauge(STAGED_AHEAD).set(0)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
