"""Request batching: collect single requests into fixed-size batches
(padding the tail) so the compiled executable shape is reused — serverless
"requests" become batched model invocations.

``EndpointBatcher`` is the one batcher: ``ServingEngine.deploy`` installs
it in front of a deployed endpoint.  It dispatches at once when the
fabric has a free slot (the queued rows spread over the free slots),
dispatches a full batch at once, and otherwise holds rows while one of
its own batches is in flight, so they leave together when that batch
frees its instance.  Each batch runs as ONE pooled invocation (one
acquire/release, one span), and ``PoolSaturated`` is backpressure: the
batch is requeued at the front and retried, never surfaced to callers as
an error.  Without a ``capacity`` signal it is a plain deadline batcher:
batches fill toward ``batch_size`` and leave ``max_wait`` after their
oldest row.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, List, Optional, Tuple

import numpy as np

from repro.core.pool import PoolSaturated
from repro.telemetry import MetricsRegistry

# how many recent per-batch fill counts the ``batch_fill`` view retains;
# the registry histogram keeps exact lifetime count/sum regardless
FILL_VIEW_LIMIT = 1024


@dataclass
class Request:
    payload: Any
    future: Future = field(default_factory=Future)
    submitted_at: float = field(default_factory=time.monotonic)


class EndpointBatcher:
    """Pool-aware batching in front of one deployed endpoint.

    ``run_batch(payloads: list) -> Future[list]`` dispatches one batch as
    a single pooled invocation through the platform (one acquire/release,
    one traced span — ``ServingEngine`` builds the closure) and resolves
    to the per-payload results in order.  With ``submit_times`` (the
    engine sets it when tracing) it is called as ``run_batch(payloads,
    submitted)``, ``submitted`` the rows' ``Request.submitted_at``, for
    the span's ``batch_wait`` phase.

    The batcher decides when a batch leaves from what it can observe: its
    queue depth, its own batches in flight, and ``capacity()``, the
    fabric's free slots for the endpoint (idle instances plus
    unprovisioned ones: ``InstancePool.idle_capacity``, or the
    cluster-wide sum).  A batch leaves under the first rule that holds,
    counted as ``dispatch.<rule>``:

    * ``free`` — a slot is free: dispatch now, the queued rows spread over
      the free slots, ``min(batch_size, ceil(queued / capacity))`` each.
      One idle instance takes every queued row up to ``batch_size``.
    * ``full`` — ``batch_size`` rows are queued: dispatch now, even with
      no free slot; the batch waits in the pool's queue and gets the
      instance straight from ``release``.
    * otherwise the rows are held while a batch of this batcher is in
      flight.  Its completion wakes the batcher after the pool has
      released the instance, so the held rows leave together as ``free``.
    * ``deadline`` — no slot free and nothing of this batcher in flight
      (a prewarm or a direct ``submit`` holds the instance): the rows
      leave ``max_wait`` after the oldest of them.  ``close()`` ends a
      wait the same way.

    Without a ``capacity`` signal (or when it raises) batches fill toward
    ``batch_size`` and leave ``max_wait`` after their oldest row.

    ``PoolSaturated`` resolving a dispatched batch is backpressure, not
    an error: the batch re-enters the queue at the *front* (admission
    order holds) and is retried after ``retry_interval``.  Requests never
    error out because the platform was momentarily full; only ``close()``
    or a genuine handler failure resolves their futures exceptionally."""

    DISPATCH_RULES = ("free", "full", "deadline")

    def __init__(self, name: str,
                 run_batch: Callable[[List[Any]], Future],
                 batch_size: int, max_wait: float = 0.01,
                 capacity: Optional[Callable[[], int]] = None,
                 retry_interval: float = 0.005,
                 clock: Callable[[], float] = time.monotonic,
                 submit_times: bool = False):
        self.name = name
        self.run_batch = run_batch
        self.submit_times = submit_times
        self.batch_size = batch_size
        self.max_wait = max_wait
        self.capacity = capacity
        self.retry_interval = retry_interval
        # deadlines are compared against Request.submitted_at (monotonic
        # domain) and pace real cond waits; injectable for tests
        self.clock = clock
        self._pending: Deque[Request] = deque()
        self._cond = threading.Condition()
        self._stop = False
        self._inflight = 0
        self.metrics = MetricsRegistry(f"batcher.{name}.")
        self._c_batches = self.metrics.counter("batches")
        self._c_requests = self.metrics.counter("requests")
        self._c_backpressure = self.metrics.counter("backpressure")
        self._c_dispatch = {rule: self.metrics.counter(f"dispatch.{rule}")
                            for rule in self.DISPATCH_RULES}
        self._h_fill = self.metrics.histogram("batch.fill")
        self.batch_fill: Deque[int] = deque(maxlen=FILL_VIEW_LIMIT)
        self._th = threading.Thread(target=self._loop, daemon=True,
                                    name=f"endpoint-batcher-{name}")
        self._th.start()

    # -- admission ------------------------------------------------------
    def submit(self, payload: Any) -> Future:
        with self._cond:
            if self._stop:
                raise RuntimeError(f"EndpointBatcher {self.name!r} is closed")
            req = Request(payload)
            self._pending.append(req)
            self._cond.notify()
        return req.future

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._pending)

    # -- batch formation ------------------------------------------------
    def _free_slots_locked(self) -> Optional[int]:
        """The fabric's free slots, or None without a signal (a torn
        signal never stalls a batch: it reads as no signal)."""
        if self.capacity is None:
            return None
        try:
            return max(0, int(self.capacity()))
        except Exception:
            return None

    def _next_batch_locked(self) -> Tuple[Optional[str], int,
                                          Optional[float]]:
        """``(rule, fill, None)`` for a batch that leaves now, else
        ``(None, 0, timeout)``: how long to wait before looking again,
        None for until woken (a submit, a batch's end, ``close``)."""
        queued = len(self._pending)
        if self._stop:
            return "deadline", min(self.batch_size, queued), None
        free = self._free_slots_locked()
        if free:
            return "free", min(self.batch_size, -(-queued // free)), None
        if queued >= self.batch_size:
            return "full", self.batch_size, None
        if free is not None and self._inflight:
            return None, 0, None
        remaining = (self._pending[0].submitted_at + self.max_wait
                     - self.clock())
        if remaining <= 0:
            return "deadline", queued, None
        return None, 0, remaining

    def _loop(self):
        while True:
            with self._cond:
                while True:
                    if not self._pending:
                        if self._stop:
                            return
                        self._cond.wait(0.1)
                        continue
                    rule, fill, timeout = self._next_batch_locked()
                    if rule is not None:
                        break
                    self._cond.wait(timeout)
                batch = [self._pending.popleft() for _ in range(fill)]
            self._dispatch(batch, rule)

    # -- dispatch + backpressure ----------------------------------------
    def _dispatch(self, batch: List[Request], rule: str):
        payloads = [r.payload for r in batch]
        try:
            fut = (self.run_batch(payloads, [r.submitted_at for r in batch])
                   if self.submit_times else self.run_batch(payloads))
        except PoolSaturated:
            self._backpressure(batch)
            return
        except BaseException as exc:
            self._fail(batch, exc)
            return
        with self._cond:
            self._inflight += 1
        fut.add_done_callback(lambda f: self._batch_done(batch, rule, f))

    def _batch_done(self, batch: List[Request], rule: str, fut: Future):
        try:
            exc = fut.exception()
        except BaseException as e:       # cancelled
            exc = e
        saturated = isinstance(exc, PoolSaturated)
        if saturated:                    # requeued while still in flight
            self._backpressure(batch)
        with self._cond:
            self._inflight -= 1
            # held rows look again: the pool released the instance before
            # this future resolved
            self._cond.notify()
        if saturated:
            return
        if exc is not None:
            self._fail(batch, exc)
            return
        results = fut.result()
        try:
            if len(results) < len(batch):
                raise RuntimeError(
                    f"batch handler returned {len(results)} results for "
                    f"{len(batch)} requests")
            for r, res in zip(batch, results):
                if not r.future.done():
                    r.future.set_result(res)
        except BaseException as e:       # noqa: BLE001
            self._fail(batch, e)
            return
        self._c_batches.inc()
        self._c_dispatch[rule].inc()
        self._c_requests.inc(len(batch))
        self._h_fill.observe(len(batch))
        self.batch_fill.append(len(batch))

    def _backpressure(self, batch: List[Request]):
        """Saturation: requeue at the front (admission order holds) and
        let the worker retry after a short pause rather than failing the
        callers."""
        self._c_backpressure.inc()
        with self._cond:
            if self._stop:
                # closing: no retry loop will run these — fail loudly
                # rather than hang callers forever
                pass
            else:
                for r in reversed(batch):
                    self._pending.appendleft(r)
                self._cond.notify()
                # wake the worker *after* a pause so the retry is not a
                # hot spin against a still-saturated pool
                threading.Timer(self.retry_interval, self._nudge).start()
                return
        self._fail(batch, RuntimeError(
            f"EndpointBatcher {self.name!r} closed while backpressured"))

    def _nudge(self):
        with self._cond:
            self._cond.notify()

    @staticmethod
    def _fail(batch: List[Request], exc: BaseException):
        for r in batch:
            if not r.future.done():
                r.future.set_exception(exc)

    # -- lifecycle ------------------------------------------------------
    def close(self, timeout: float = 5.0):
        """Graceful: the worker drains everything pending (each drained
        batch still dispatches through ``run_batch``), then exits."""
        with self._cond:
            if self._stop:
                return
            self._stop = True
            self._cond.notify_all()
        self._th.join(timeout=timeout)
        # worker gone (or stuck): fail stragglers rather than hang callers
        with self._cond:
            stragglers = list(self._pending)
            self._pending.clear()
        self._fail(stragglers, RuntimeError(
            f"EndpointBatcher {self.name!r} closed"))

    def stats(self) -> dict:
        summary = self._h_fill.summary()
        with self._cond:
            depth, inflight = len(self._pending), self._inflight
        return {"batches": self._c_batches.value,
                "requests": self._c_requests.value,
                "backpressure": self._c_backpressure.value,
                "dispatch": {rule: c.value
                             for rule, c in self._c_dispatch.items()},
                "mean_fill": summary["mean"] if summary["count"] else 0.0,
                "queue_depth": depth, "inflight": inflight}

    def metrics_snapshot(self) -> dict:
        return self.metrics.snapshot()


def pad_batch(payloads: List[np.ndarray], batch_size: int) -> np.ndarray:
    """Stack variable-count payloads to a fixed batch (repeat last row)."""
    arr = np.stack(payloads)
    if len(payloads) < batch_size:
        pad = np.repeat(arr[-1:], batch_size - len(payloads), axis=0)
        arr = np.concatenate([arr, pad], axis=0)
    return arr
