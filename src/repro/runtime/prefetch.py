"""Bounded, order-preserving prefetch executor for host-side plan work.

The producer side of the pipeline (sampling -> online split -> feature load)
is embarrassingly parallel across mini-batches once each batch derives its
own RNG stream, but the *consumer* (the jitted train step) must receive
batches in epoch order so optimizer updates match serial execution exactly.
``OrderedPrefetcher`` therefore runs ``fn(index)`` on a small thread pool,
holds completed items in a reorder buffer, and hands them out strictly by
index. A ticket semaphore bounds how far the producers may run ahead
(``depth`` outstanding items), which bounds host memory for staged feature
blocks.

Supervision (docs/ROBUSTNESS.md):

  * **Retry** — a build raising :class:`~repro.faults.RetryableError` is
    re-attempted in place under a :class:`~repro.faults.RetryPolicy`
    (bounded attempts, exponential backoff). The retried build keeps its
    ticket and its delivery slot, so downstream ordering is untouched;
    retry is *correct* because builds are pure functions of
    ``(seed, epoch, batch)`` under the keyed-RNG discipline.
  * **Crash respawn** — a worker dying on :class:`~repro.faults.WorkerCrash`
    requeues its claimed index, releases its ticket, and exits; the
    consumer-side supervisor (run inside the delivery wait loop) spawns one
    replacement per crash, so capacity recovers without any background
    babysitter thread.
  * **Watchdog** — with ``stall_timeout_s`` set, a delivery that waits
    longer than the budget raises :class:`~repro.faults.PipelineStallError`
    naming the stuck index, the live producer threads, and the reorder-queue
    occupancy, instead of blocking the epoch forever.

All recovery events are counted in :class:`PrefetchStats` and emitted as
``fault/*`` obs metrics.

Worker exceptions other than the two fault types above are captured and
re-raised at the *delivery point* of the failing index, so the consumer sees
the error exactly where the batch would have been, and ``close()`` (also
called by ``__exit__`` and on consumer-side errors) always leaves the pool
joined and the queue drained — threads that fail to join within 10s are
logged by name and surfaced as ``leaked_threads``.
"""
from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from repro.faults.errors import PipelineStallError, WorkerCrash
from repro.faults.retry import RetryPolicy, retry_call
from repro.obs import NULL_OBS

log = logging.getLogger("repro.prefetch")

_JOIN_TIMEOUT_S = 10.0

# the spawn index of the producer worker running on this thread
_worker = threading.local()


def current_worker() -> int:
    """The index of the prefetch worker on the calling thread (its spawn
    order, as in its name ``plan-producer-<n>``); -1 off the pool."""
    return getattr(_worker, "index", -1)


@dataclass
class PrefetchStats:
    """Occupancy/wait/recovery counters for one prefetcher lifetime."""

    delivered: int = 0
    occupancy_sum: int = 0  # reorder-buffer size summed at each delivery
    consumer_waits: int = 0  # deliveries that blocked on an unfinished batch
    occupancy_max: int = 0
    retries: int = 0  # transient build failures re-attempted in place
    worker_crashes: int = 0  # producer threads that died (WorkerCrash)
    respawns: int = 0  # replacement workers started by the supervisor
    leaked_threads: int = 0  # threads that failed to join at close()
    samples: list = field(default_factory=list)

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.delivered if self.delivered else 0.0

    def as_dict(self) -> dict:
        return {
            "delivered": self.delivered,
            "mean_occupancy": self.mean_occupancy,
            "max_occupancy": self.occupancy_max,
            "consumer_waits": self.consumer_waits,
            "retries": self.retries,
            "worker_crashes": self.worker_crashes,
            "respawns": self.respawns,
            "leaked_threads": self.leaked_threads,
        }


class OrderedPrefetcher:
    """Run ``fn(i)`` for ``i in range(num_items)`` on ``workers`` threads,
    delivering results in index order with at most ``depth`` in flight."""

    def __init__(
        self,
        fn: Callable[[int], Any],
        num_items: int,
        depth: int = 4,
        workers: int = 2,
        retry: RetryPolicy | None = None,
        stall_timeout_s: float | None = None,
        obs=NULL_OBS,
    ):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if stall_timeout_s is not None and stall_timeout_s <= 0:
            raise ValueError(
                f"stall_timeout_s must be positive, got {stall_timeout_s}"
            )
        self._fn = fn
        self._num_items = num_items
        self._retry = retry or RetryPolicy()
        self._stall_timeout_s = stall_timeout_s
        self._obs = obs
        self._tickets = threading.Semaphore(depth)
        self._lock = threading.Condition()
        self._buffer: dict[int, tuple[Any, BaseException | None]] = {}
        self._next_claim = 0
        self._requeue: list[int] = []  # indices orphaned by crashed workers
        self._spawned = 0
        self._stop = threading.Event()
        self.stats = PrefetchStats()
        self._threads: list[threading.Thread] = []
        for _ in range(min(workers, max(num_items, 1))):
            self._spawn_worker()

    def _spawn_worker(self) -> None:
        t = threading.Thread(
            target=self._work,
            args=(self._spawned,),
            name=f"plan-producer-{self._spawned}",
            daemon=True,
        )
        self._spawned += 1
        self._threads.append(t)
        t.start()

    # ------------------------------------------------------------------ #
    def _claim(self) -> int:
        with self._lock:
            if self._requeue:
                return self._requeue.pop()
            if self._next_claim >= self._num_items:
                return -1
            idx = self._next_claim
            self._next_claim += 1
            return idx

    def _on_retry(self, attempt: int, err: BaseException) -> None:
        with self._lock:
            self.stats.retries += 1
        self._obs.count("fault/producer_retries", 1)
        log.warning(
            "transient producer fault (attempt %d, backing off %.3fs): %s",
            attempt, self._retry.delay_s(attempt), err,
        )

    def _work(self, worker: int) -> None:
        _worker.index = worker
        while not self._stop.is_set():
            self._tickets.acquire()
            if self._stop.is_set():
                break
            idx = self._claim()
            if idx < 0:
                # let fellow workers observe exhaustion too
                self._tickets.release()
                break
            try:
                result, err = (
                    retry_call(
                        lambda i=idx: self._fn(i),
                        self._retry,
                        on_retry=self._on_retry,
                        cancel=self._stop,
                    ),
                    None,
                )
            except WorkerCrash:
                # simulated hard thread death: hand the batch back, free the
                # ticket, and exit — the consumer-side supervisor respawns.
                with self._lock:
                    self._requeue.append(idx)
                    self.stats.worker_crashes += 1
                    self._lock.notify_all()
                self._tickets.release()
                self._obs.count("fault/worker_crashes", 1)
                self._obs.instant(
                    "fault/worker_crash",
                    {"index": idx, "thread": threading.current_thread().name},
                )
                return
            except BaseException as e:  # noqa: BLE001 - delivered to consumer
                result, err = None, e
            with self._lock:
                self._buffer[idx] = (result, err)
                self._lock.notify_all()

    # ------------------------------------------------------------------ #
    def _supervise(self) -> None:
        """Respawn one worker per recorded crash. Caller holds ``_lock``."""
        while (
            self.stats.respawns < self.stats.worker_crashes
            and not self._stop.is_set()
        ):
            self.stats.respawns += 1
            self._obs.count("fault/worker_respawns", 1)
            self._spawn_worker()
            log.warning(
                "respawned producer worker (%d crash(es), %d respawn(s))",
                self.stats.worker_crashes, self.stats.respawns,
            )

    def __iter__(self):
        try:
            for idx in range(self._num_items):
                with self._lock:
                    # restore pool capacity for any crash recorded since the
                    # last delivery, even when a surviving worker already
                    # drained the requeue — respawn is a function of the
                    # crash/respawn counters, not of wait timing
                    self._supervise()
                    if idx not in self._buffer:
                        self.stats.consumer_waits += 1
                    waited_since = time.perf_counter()
                    while idx not in self._buffer:
                        if self._stop.is_set():
                            raise RuntimeError("prefetcher closed mid-iteration")
                        self._supervise()
                        self._lock.wait(timeout=0.1)
                        waited = time.perf_counter() - waited_since
                        if (
                            self._stall_timeout_s is not None
                            and waited > self._stall_timeout_s
                            and idx not in self._buffer
                        ):
                            live = [
                                t.name for t in self._threads if t.is_alive()
                            ]
                            self._obs.count("fault/pipeline_stalls", 1)
                            self._obs.instant(
                                "fault/pipeline_stall",
                                {"index": idx, "waited_s": round(waited, 3)},
                            )
                            raise PipelineStallError(
                                index=idx,
                                waited_s=waited,
                                live_threads=live,
                                occupancy=len(self._buffer),
                                next_claim=self._next_claim,
                                delivered=self.stats.delivered,
                            )
                    self.stats.occupancy_sum += len(self._buffer)
                    self.stats.occupancy_max = max(
                        self.stats.occupancy_max, len(self._buffer)
                    )
                    self.stats.delivered += 1
                    result, err = self._buffer.pop(idx)
                # free the ticket before (possibly) raising so close() never
                # deadlocks on a full queue
                self._tickets.release()
                if err is not None:
                    raise err
                yield result
        finally:
            self.close()

    # ------------------------------------------------------------------ #
    def close(self) -> None:
        """Stop producers and join them. Idempotent."""
        self._stop.set()
        # unblock any worker parked on the ticket semaphore
        for _ in self._threads:
            self._tickets.release()
        with self._lock:
            self._lock.notify_all()
        leaked = []
        for t in self._threads:
            t.join(timeout=_JOIN_TIMEOUT_S)
            if t.is_alive():
                leaked.append(t.name)
        if leaked:
            log.warning(
                "prefetcher close(): %d thread(s) failed to join within "
                "%.0fs and are leaked: %s",
                len(leaked), _JOIN_TIMEOUT_S, ", ".join(leaked),
            )
            self.stats.leaked_threads = len(leaked)
            self._obs.count("fault/leaked_threads", len(leaked))
        self._threads = [t for t in self._threads if t.is_alive()]

    @property
    def closed(self) -> bool:
        return self._stop.is_set() and not self._threads

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()
