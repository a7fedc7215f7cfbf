"""Dynamic micro-batching with backpressure-based admission control,
per-row deadlines, and dead-worker fast-fail.

Online traffic arrives one row at a time; the accelerator wants bucketed
batches (serving/engine.py). The micro-batcher sits between: requests
queue, a worker thread flushes a batch when either ``max_batch`` rows
are waiting (throughput trigger) or the OLDEST row has waited
``max_wait_ms`` (latency trigger), and the engine's bucket padding turns
whatever was gathered into a compiled shape. This is the standard
dynamic-batching contract (TF-Serving/Triton); the BigDL lineage analog
is the DLClassifier's per-partition batching, which had Spark to do the
gathering — here a queue + worker thread replace the RDD machinery.

Admission control is backpressure by queue depth: when ``max_queue``
rows are already pending, ``submit`` raises :class:`AdmissionError`
IMMEDIATELY (fast-reject) instead of letting latency grow without bound
— the caller (server.py) maps it to HTTP 429 so load sheds at the edge.

Robustness (ISSUE 6):

* **deadlines** — ``submit(row, deadline=t)`` marks the row with an
  absolute expiry on the batcher's clock; expired rows are dropped at
  drain time BEFORE any compute is spent on them (and a row already
  past its deadline is rejected at submit), resolving their futures
  with :class:`DeadlineExceeded` — server.py maps it to HTTP 504;
* **dead-worker fast-fail** — if the worker thread dies (a
  ``worker_fatal`` exception out of the engine, or any bug in the loop
  itself), every pending future is failed with :class:`WorkerDied` and
  subsequent ``submit`` calls raise it immediately, instead of
  enqueueing into a queue nobody drains until the caller's own timeout;
* **deterministic close** — ``close()`` either flushes every pending
  row (live worker / no worker) or fails them all with
  :class:`WorkerDied` (dead or wedged worker); nothing is left hanging;
* **watchdog surface** — ``alive()``/``busy()``/``heartbeat_age()``/
  ``declare_dead()`` let serving/watchdog.py detect a wedged (alive but
  stuck) worker and fail it fast.

Determinism for tests: the flush decision is a pure function of the
injected ``clock`` (``_flush_ready``/``pump``), so trigger, deadline,
and expiry semantics are testable without threads or real time.
"""

from __future__ import annotations

import collections
import inspect
import logging
import threading
import time
from typing import Callable, Optional

import numpy as np

from bigdl_tpu.obs.spans import (get_tracer as _get_tracer,
                                 span as _obs_span)
from bigdl_tpu.serving.reqtrace import get as _get_reqtracer

__all__ = ["AdmissionError", "DeadlineExceeded", "WorkerDied",
           "MicroBatcher"]

logger = logging.getLogger(__name__)


class AdmissionError(RuntimeError):
    """Queue at capacity — request rejected at admission (HTTP 429)."""


class DeadlineExceeded(RuntimeError):
    """Request deadline expired before compute (HTTP 504) — the row was
    dropped unprocessed, so the caller may safely retry elsewhere."""


class WorkerDied(RuntimeError):
    """The serving worker thread is dead or wedged; the request failed
    fast instead of waiting out its timeout (HTTP 503)."""


_CALLBACKS = threading.Lock()  # every future's callback list


class _Future:
    """Minimal thread-safe future (no concurrent.futures executor to
    own it — the batcher resolves it from its worker thread)."""

    __slots__ = ("_event", "_value", "_exc", "_callbacks")

    def __init__(self):
        self._event = threading.Event()
        self._value = None
        self._exc = None
        self._callbacks = []  # None once resolved

    def set_result(self, v) -> None:
        self._value = v
        self._resolve()

    def set_exception(self, e: BaseException) -> None:
        self._exc = e
        self._resolve()

    def _resolve(self) -> None:
        self._event.set()
        with _CALLBACKS:
            callbacks, self._callbacks = self._callbacks, None
        for fn in callbacks or ():
            try:
                fn(self)
            except Exception:  # the resolving thread must keep running
                logger.exception("future done-callback failed")

    def add_done_callback(self, fn) -> None:
        """Call ``fn(future)`` once, when the future resolves: on the
        resolving thread and under whatever lock it holds (the decode
        engine's, for a request's future), so ``fn`` must not block; at
        once on this thread if it has resolved already."""
        with _CALLBACKS:
            if self._callbacks is not None:
                self._callbacks.append(fn)
                return
        fn(self)

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("batched request did not complete in time")
        if self._exc is not None:
            raise self._exc
        return self._value


class _Pending:
    __slots__ = ("row", "future", "t_enqueue", "deadline", "rid")

    def __init__(self, row, future, t, deadline=None, rid=None):
        self.row, self.future, self.t_enqueue = row, future, t
        self.deadline = deadline
        self.rid = rid


class MicroBatcher:
    """Gather single-row requests into engine batches.

    ``predict_fn(batch_rows) -> scores`` is typically
    ``engine.predict_scores``; rows of one flush are stacked along axis
    0 and results are split back per request.

    ``clock`` is injectable (monotonic seconds) for deterministic tests;
    with ``start=False`` no worker thread runs and the test drives
    :meth:`pump` manually.
    """

    def __init__(self, predict_fn: Callable, *, max_batch: int = 32,
                 max_wait_ms: float = 5.0, max_queue: int = 256,
                 clock: Callable[[], float] = time.monotonic,
                 metrics=None, start: bool = True):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.predict_fn = predict_fn
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1000.0
        self.max_queue = int(max_queue)
        self.clock = clock
        self._pending: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._wakeup = threading.Condition(self._lock)
        self._closed = False
        self._thread = None
        self._worker_error: Optional[BaseException] = None
        self._last_beat = clock()
        self._in_flush = False
        # ISSUE 15: when the engine forward can attribute compute back
        # to request ids (engine.predict_scores grew a ``rids`` kwarg),
        # forward them; a plain fn gets a coarse whole-flush window
        try:
            self._fn_takes_rids = "rids" in inspect.signature(
                predict_fn).parameters
        except (TypeError, ValueError):
            self._fn_takes_rids = False

        if metrics is not None:
            self._m_submitted = metrics.counter(
                "batcher_rows_submitted_total", "rows accepted by submit")
            self._m_rejected = metrics.counter(
                "batcher_rows_rejected_total",
                "rows fast-rejected at admission (queue full)")
            self._m_expired = metrics.counter(
                "batcher_rows_expired_total",
                "rows dropped before compute (deadline exceeded)")
            self._m_dead = metrics.counter(
                "batcher_dead_submit_total",
                "submits fast-failed because the worker is dead")
            self._m_flushes = metrics.counter(
                "batcher_flushes_total", "micro-batches dispatched")
            self._m_wait = metrics.histogram(
                "batcher_queue_wait_ms", "enqueue -> flush wait per row")
            metrics.gauge("batcher_queue_depth", "rows currently queued",
                          fn=lambda: len(self._pending))
            metrics.gauge("batcher_worker_up",
                          "1 while the flush worker is healthy",
                          fn=lambda: 0.0 if self._worker_error else 1.0)
        else:
            self._m_submitted = self._m_rejected = self._m_flushes = None
            self._m_expired = self._m_dead = self._m_wait = None

        if start:
            self._thread = threading.Thread(target=self._worker,
                                            name="micro-batcher",
                                            daemon=True)
            self._thread.start()

    # --------------------------------------------------------------- submit
    def submit(self, row, deadline: Optional[float] = None,
               rid: Optional[str] = None) -> _Future:
        """Queue one input row; returns a future resolving to its score
        row. ``deadline`` is an absolute time on the batcher's clock —
        rows past it are dropped before compute (future raises
        :class:`DeadlineExceeded`). ``rid`` tags the row with its
        request id for lifecycle tracing (ISSUE 15); None when tracing
        is off. Raises :class:`AdmissionError` without blocking when
        the queue is at ``max_queue`` (backpressure fast-reject) and
        :class:`WorkerDied` when the worker thread is gone (nothing
        would ever drain the queue)."""
        fut = _Future()
        now = self.clock()
        with self._lock:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if self._worker_error is not None or (
                    self._thread is not None
                    and not self._thread.is_alive()):
                if self._m_dead is not None:
                    self._m_dead.inc()
                raise WorkerDied(
                    "micro-batcher worker is dead: "
                    f"{self._worker_error or 'thread exited'}")
            if deadline is not None and now >= deadline:
                if self._m_expired is not None:
                    self._m_expired.inc()
                raise DeadlineExceeded(
                    f"deadline expired {now - deadline:.3f}s before "
                    f"submit")
            if len(self._pending) >= self.max_queue:
                if self._m_rejected is not None:
                    self._m_rejected.inc()
                raise AdmissionError(
                    f"queue at capacity ({self.max_queue} rows pending)")
            self._pending.append(_Pending(row, fut, now, deadline, rid))
            if self._m_submitted is not None:
                self._m_submitted.inc()
            self._wakeup.notify()
        if rid is not None:
            rt = _get_reqtracer()
            if rt is not None:
                rt.note_queued(rid)
        return fut

    @property
    def queue_depth(self) -> int:
        return len(self._pending)

    # ------------------------------------------------------ watchdog surface
    def alive(self) -> bool:
        """False once the worker thread has died or been declared dead
        (threadless test mode counts as alive — pump() is the worker)."""
        if self._worker_error is not None:
            return False
        return self._thread is None or self._thread.is_alive()

    def busy(self) -> bool:
        """True while there is work a healthy worker should be making
        progress on (queued rows or an in-flight flush)."""
        return bool(self._pending) or self._in_flush

    def heartbeat_age(self, now: Optional[float] = None) -> float:
        """Seconds since the worker last proved liveness."""
        return (self.clock() if now is None else now) - self._last_beat

    @property
    def worker_error(self) -> Optional[BaseException]:
        return self._worker_error

    def declare_dead(self, exc: BaseException) -> None:
        """Mark the worker dead (watchdog verdict on a wedged thread, or
        the worker's own epitaph): every pending future fails with
        ``exc`` and subsequent submits raise :class:`WorkerDied` fast."""
        with self._lock:
            if self._worker_error is None:
                self._worker_error = exc
            dead = list(self._pending)
            self._pending.clear()
            self._wakeup.notify_all()
        for p in dead:
            p.future.set_exception(
                exc if isinstance(exc, WorkerDied)
                else WorkerDied(f"micro-batcher worker died: {exc}"))

    # ---------------------------------------------------------- flush logic
    def _flush_ready(self, now: float) -> bool:
        """Pure trigger decision: full batch waiting, the oldest row has
        aged past max_wait, or expired rows need dropping."""
        if not self._pending:
            return False
        if len(self._pending) >= self.max_batch:
            return True
        head = self._pending[0]
        if head.deadline is not None and now >= head.deadline:
            return True
        return (now - head.t_enqueue) >= self.max_wait_s

    def _drain(self, now: float) -> list:
        """Pop up to max_batch live rows, expiring dead-on-arrival ones
        (deadline passed) BEFORE any compute is spent on them."""
        batch = []
        while self._pending and len(batch) < self.max_batch:
            p = self._pending.popleft()
            if p.deadline is not None and now >= p.deadline:
                if self._m_expired is not None:
                    self._m_expired.inc()
                p.future.set_exception(DeadlineExceeded(
                    f"deadline expired {now - p.deadline:.3f}s before "
                    f"compute (queued {now - p.t_enqueue:.3f}s)"))
                continue
            batch.append(p)
        return batch

    def _flush(self, batch: list, now: float) -> None:
        if not batch:
            return
        if self._m_wait is not None:
            for p in batch:
                self._m_wait.observe((now - p.t_enqueue) * 1000.0)
        tr = _get_tracer()
        if tr is not None:
            # queue wait is retrospective (enqueue happened on another
            # thread): back-date one span PER ROW so every request's
            # wait — not just the oldest's — lands on the timeline, and
            # the request-path reads queue_wait -> batch_assembly ->
            # compute (per-row accounting: ISSUE 15 satellite)
            t1 = tr.clock()
            for p in batch:
                args = {"rows": len(batch)}
                if p.rid is not None:
                    args["rid"] = p.rid
                tr.record("queue_wait",
                          t1 - max(now - p.t_enqueue, 0.0), t1,
                          depth=0, args=args)
        rt = _get_reqtracer()
        if rt is not None:
            for p in batch:
                if p.rid is not None:
                    rt.note_dequeued(p.rid)
        rids = None
        if rt is not None and self._fn_takes_rids:
            rids = [p.rid for p in batch]
        try:
            # queue_wait ended at drain; assembly (stack) and compute
            # (engine forward) are the next spans on the request path
            with _obs_span("batch_assembly", rows=len(batch)):
                stacked = np.stack([np.asarray(p.row) for p in batch])
            with _obs_span("compute", rows=len(batch)):
                if rids is not None:
                    scores = self.predict_fn(stacked, rids=rids)
                else:
                    t0c = rt.clock() if rt is not None else 0.0
                    scores = self.predict_fn(stacked)
                    if rt is not None:
                        t1c = rt.clock()
                        for p in batch:
                            if p.rid is not None:
                                rt.note_compute(p.rid, t0c, t1c)
        except BaseException as e:  # resolve every waiter, never hang them
            for p in batch:
                p.future.set_exception(e)
            if getattr(e, "worker_fatal", False):
                raise  # fatal to the WORKER: die so submits fast-fail
            return
        if self._m_flushes is not None:
            self._m_flushes.inc()
        for p, s in zip(batch, np.asarray(scores)):
            p.future.set_result(s)

    def pump(self, now: Optional[float] = None) -> int:
        """Flush at most one micro-batch if a trigger fired; returns the
        number of rows flushed (expired rows count — they were resolved).
        The worker thread calls this in a loop; tests call it directly
        with an injected ``now``."""
        now = self.clock() if now is None else now
        with self._lock:
            if not self._flush_ready(now):
                return 0
            depth0 = len(self._pending)
            batch = self._drain(now)
            settled = depth0 - len(self._pending)  # flushed + expired
        # engine call happens OUTSIDE the lock: submits stay wait-free
        # while the forward runs
        self._flush(batch, now)
        return settled

    # --------------------------------------------------------------- worker
    def _worker(self) -> None:
        try:
            while True:
                with self._lock:
                    self._last_beat = self.clock()
                    while not self._pending and not self._closed:
                        self._wakeup.wait()
                        self._last_beat = self.clock()
                    if self._closed and not self._pending:
                        return
                    now = self.clock()
                    if not self._flush_ready(now):
                        # sleep until the oldest row's deadline (or an
                        # earlier submit fills the batch and notifies)
                        head = self._pending[0]
                        wake = head.t_enqueue + self.max_wait_s
                        if head.deadline is not None:
                            wake = min(wake, head.deadline)
                        self._wakeup.wait(timeout=max(wake - now, 0.0))
                        continue
                    batch = self._drain(now)
                    self._in_flush = True
                try:
                    self._flush(batch, self.clock())
                finally:
                    self._in_flush = False
        except BaseException as e:
            # the worker is the only drain: record the cause, fail every
            # waiter, and let submit() fast-fail from here on
            self.declare_dead(e)

    def close(self, timeout: float = 10.0) -> None:
        """Stop accepting work, then deterministically settle every
        pending row: flush it (live worker, or no worker at all) or fail
        it with :class:`WorkerDied` (dead/wedged worker). Nothing is
        left for callers to time out on."""
        with self._lock:
            self._closed = True
            self._wakeup.notify_all()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                # wedged mid-flush: its waiters cannot be flushed twice,
                # but everything still queued gets a deterministic error
                self.declare_dead(WorkerDied(
                    f"worker did not drain within {timeout}s at close"))
                return
        if self._worker_error is not None:
            self.declare_dead(self._worker_error)
            return
        # no worker (tests / start=False) or clean worker exit that left
        # rows (closed while flushing): drain synchronously
        while self._pending:
            with self._lock:
                batch = self._drain(self.clock())
            self._flush(batch, self.clock())
