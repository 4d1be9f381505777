"""Dynamic request batching: many concurrent /predict calls, few dispatches.

The reference route (DL4jServeRouteBuilder.java) and its mirror
(streaming/serving.py pre-rewrite) run ``output()`` once PER RECORD:
that is one dispatch per request for a batch-1 program —
the training-time op-granularity gap (SURVEY §3.1) reappearing at
inference. The batcher closes it the same way fit_batches closed the
training side: a bounded queue coalesces whatever requests are in flight
into ONE bucket-shaped batch per dispatch.

Batch shapes come from the shared bucketing policy (ops/dispatch.py
``bucket_size``): a flushed batch of any size pads up to the
powers-of-two-and-1.5x ladder, so the steady state compiles O(log
max_batch) programs total and then never retraces — the zero-retrace hot
path, now serving. Pad rows are inference-only and provably inert (BN uses
running stats, dropout is off, every op is row-independent; the
equivalence test asserts byte-identical rows against direct ``output()``).

Flow control, in order:
  * bucket-full flush     — max_batch real rows waiting -> dispatch now;
  * deadline flush        — the OLDEST queued request has waited
                            max_wait_ms -> dispatch whatever is here
                            (bounded added latency);
  * backpressure          — queue past queue_capacity rows -> submit()
                            raises QueueFullError (the HTTP layer turns
                            this into 429, the standard shed signal);
  * per-request timeout   — a request older than its deadline is answered
                            with RequestTimeoutError (504), never silently
                            dropped.

Failure semantics (serving/resilience.py, the serving twin of PR 3):
  * hung dispatch         — ``watchdog_s > 0`` arms an InferenceWatchdog
                            around every ``infer_fn`` call (completion
                            fenced by the infer fn's own np.asarray host
                            readback). On expiry the
                            in-flight futures fail with ModelWedgedError
                            (a diagnosis, not a 504-by-rot), the wedged
                            worker thread is abandoned behind a
                            generation fence (its late completion
                            resolves nothing) and a replacement worker
                            takes over the queue, so the batcher survives
                            a device call that hangs (~0 CPU, no error,
                            forever).
  * dead worker           — an uncaught error in the worker loop fails
                            the in-flight and queued futures and marks
                            the batcher dead; submit() then fast-fails
                            with WorkerDeadError instead of queueing
                            requests nobody will serve.
  * per-dispatch outcome  — ``on_outcome(ok, exc)`` feeds the engine's
                            per-model circuit breaker; ``on_wedged(info)``
                            lets it trip the breaker + journal the wedge.
  * drain()               — wait (bounded) for queue + in-flight to
                            empty; stop() fails whatever remains, in
                            flight included — a stopped server leaves no
                            client blocked on a future nobody resolves.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Callable, Optional

import numpy as np

from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.serving.resilience import (
    InferenceWatchdog,
    ModelWedgedError,
    WorkerDeadError,
)
from deeplearning4j_tpu.serving.telemetry import ServingStats


class QueueFullError(RuntimeError):
    """Backpressure: the request queue is at capacity (HTTP 429)."""


class RequestTimeoutError(TimeoutError):
    """The request's deadline expired before its batch ran (HTTP 504)."""


def _resolve(fut: Future, result=None, exception=None) -> bool:
    """Resolve a future if the client is still waiting. Returns False for
    futures already done OR cancelled by a timed-out waiter; the done()
    pre-check races the waiter's cancel(), so InvalidStateError closes
    the window — a abandoned request must not crash the worker or count
    as a completion."""
    try:
        if fut.done():
            return False
        if exception is not None:
            fut.set_exception(exception)
        else:
            fut.set_result(result)
        return True
    except Exception:  # noqa: BLE001 — InvalidStateError/CancelledError race
        return False


class _Request:
    __slots__ = ("rows", "future", "deadline", "enqueued", "rid")

    def __init__(self, rows: np.ndarray, deadline: float,
                 rid: Optional[int] = None) -> None:
        self.rows = rows
        self.future: Future = Future()
        self.deadline = deadline
        self.enqueued = time.monotonic()
        # observability request id (ISSUE 7): assigned at the engine
        # boundary, rides the queue, and surfaces in the serve.batch
        # span's request_ids — the thread that joins a request's span to
        # the coalesced batch (and, via span parenting on the worker
        # thread, to the jit dispatch underneath)
        self.rid = rid


class DynamicBatcher:
    """Coalesce concurrent row-wise inference requests into bucket batches.

    ``infer_fn(batch [N, ...]) -> np.ndarray [N, ...]`` is the model call;
    it is invoked from the single worker thread (so models whose output
    path is not thread-safe need no extra lock) and is expected to pad
    internally via the shared inference bucketing (both containers'
    ``output()`` — nn/multilayer.py / nn/graph.py — already do).
    """

    def __init__(self, infer_fn: Callable[[np.ndarray], np.ndarray], *,
                 max_batch: int = 64, max_wait_ms: float = 10.0,
                 queue_capacity: int = 512,
                 default_timeout_s: float = 60.0,
                 stats: Optional[ServingStats] = None,
                 watchdog_s: float = 0.0,
                 on_wedged: Optional[Callable[[dict], None]] = None,
                 on_outcome: Optional[Callable] = None) -> None:
        if max_batch < 1 or queue_capacity < 1:
            raise ValueError("max_batch and queue_capacity must be >= 1")
        self._infer = infer_fn
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.queue_capacity = int(queue_capacity)
        self.default_timeout_s = float(default_timeout_s)
        self.stats = stats if stats is not None else ServingStats()
        # resilience hooks (serving/resilience.py): on_outcome(ok, exc)
        # feeds the engine's circuit breaker per dispatch; on_wedged(info)
        # fires after the watchdog replaced a wedged worker
        self._on_outcome = on_outcome
        self._on_wedged = on_wedged
        self._q: deque = deque()
        self._q_rows = 0
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._running = True
        # worker-generation fence: every worker thread carries the gen it
        # was born with; the watchdog bumps it when abandoning a wedged
        # worker, so a zombie waking up later takes no batch and resolves
        # nothing. _inflight is the batch currently inside infer_fn —
        # (gen, taken requests) — the set stop()/the watchdog must fail.
        self._gen = 0
        self._inflight: Optional[tuple] = None
        self._dead: Optional[str] = None  # uncaught-worker-error reason
        self.watchdog = (InferenceWatchdog(watchdog_s, self._wedge_handler)
                         if watchdog_s > 0 else None)
        self._worker = self._spawn_worker()

    def _spawn_worker(self) -> threading.Thread:
        t = threading.Thread(target=self._run, args=(self._gen,),
                             daemon=True,
                             name=f"dynamic-batcher-g{self._gen}")
        t.start()
        return t

    # -- client side ------------------------------------------------------
    def submit(self, rows, timeout_s: Optional[float] = None,
               rid: Optional[int] = None) -> Future:
        """Enqueue ``rows`` ([k, ...] — one request may carry several rows)
        and return a Future resolving to the [k, ...] outputs. Raises
        QueueFullError when the queue is at capacity (backpressure).
        ``rid`` is the engine-assigned observability request id."""
        rows = np.asarray(rows)
        if rows.ndim < 1 or rows.shape[0] < 1:
            raise ValueError("submit() needs at least one row")
        self.stats.record_request()
        deadline = time.monotonic() + (timeout_s if timeout_s is not None
                                       else self.default_timeout_s)
        req = _Request(rows, deadline, rid=rid)
        with self._cond:
            if not self._running:
                raise RuntimeError("batcher is stopped")
            if self._dead is not None:
                raise WorkerDeadError(
                    f"batcher worker died ({self._dead}); requests would "
                    "queue forever")
            # belt-and-braces: a worker that died WITHOUT tripping the
            # outer handler (interpreter teardown, a raising thread-state
            # edge) must still fast-fail here, not rot requests to 504
            if not self._worker.is_alive():
                self._dead = "worker thread not alive"
                self.stats.record_worker_death()
                raise WorkerDeadError(
                    "batcher worker thread is dead; requests would queue "
                    "forever")
            # an EMPTY queue always admits (an oversize request larger
            # than queue_capacity passes through as its own batch —
            # _take_batch handles it; a hard reject would 429 it forever)
            if (self._q_rows > 0
                    and self._q_rows + rows.shape[0] > self.queue_capacity):
                self.stats.record_rejected()
                raise QueueFullError(
                    f"queue at capacity ({self._q_rows}/"
                    f"{self.queue_capacity} rows)")
            self._q.append(req)
            self._q_rows += rows.shape[0]
            self.stats.set_queue_depth(self._q_rows)
            self._cond.notify_all()
        return req.future

    def predict(self, rows, timeout_s: Optional[float] = None,
                rid: Optional[int] = None) -> np.ndarray:
        """submit() + wait; raises RequestTimeoutError past the deadline."""
        budget = timeout_s if timeout_s is not None else self.default_timeout_s
        fut = self.submit(rows, timeout_s=budget, rid=rid)
        try:
            return fut.result(timeout=budget + self.max_wait_s)
        except RequestTimeoutError:
            raise  # worker-side expiry — already counted in _take_batch
        # on 3.10 concurrent.futures.TimeoutError is NOT the builtin
        except (TimeoutError, FutureTimeoutError) as e:
            # cancel so a worker finishing the batch later doesn't record
            # a phantom completion/latency for a response nobody received
            fut.cancel()
            self.stats.record_timeout()
            raise RequestTimeoutError("request timed out in queue") from e

    def drain(self, timeout_s: float = 20.0) -> bool:
        """Wait (bounded) for the queue AND the in-flight batch to empty —
        the graceful half of shutdown: admission is the caller's to stop
        (the engine 503s new requests first), completion is ours to wait
        for. True when everything admitted was answered in time."""
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        with self._cond:
            while (self._q or self._inflight is not None) \
                    and self._dead is None:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(timeout=left)
            return self._dead is None

    def stop(self, timeout_s: float = 5.0) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        self._worker.join(timeout=timeout_s)
        if self.watchdog is not None:
            self.watchdog.stop()
        # fail whatever is still queued OR in flight — a stopped server
        # must not leave clients blocked on futures nobody will resolve.
        # The in-flight batch matters exactly when the worker did not
        # join: a wedged infer call holds its taken requests outside the
        # queue, and abandoning them would be the silent-504 failure mode
        # this plane exists to kill. _resolve fences the race with a
        # worker that completes late.
        with self._cond:
            inflight = self._inflight
            self._inflight = None
            self._gen += 1  # fence a still-running worker out
            while self._q:
                req = self._q.popleft()
                _resolve(req.future,
                         exception=RuntimeError("batcher stopped"))
            self._q_rows = 0
            self.stats.set_queue_depth(0)
        if inflight is not None:
            for req in inflight[1]:
                _resolve(req.future, exception=RuntimeError(
                    "batcher stopped with this request in flight"))

    # -- worker side ------------------------------------------------------
    def _take_batch(self, gen: int):
        """Under the lock: wait for work, honor the flush rules, and pop
        whole requests up to max_batch rows (one oversize request passes
        through alone — its rows are already a batch). Returns None when
        this worker should exit (stopped, or its generation was fenced
        out by the watchdog). A non-empty take is recorded as the
        in-flight batch BEFORE the lock drops, so stop()/the watchdog
        always see the requests the worker is holding."""
        with self._cond:
            while self._running and self._gen == gen and not self._q:
                self._cond.wait()
            if not self._q or self._gen != gen:
                return None  # stopped/fenced and drained
            flush_at = self._q[0].enqueued + self.max_wait_s
            while (self._running and self._gen == gen
                   and self._q_rows < self.max_batch
                   and time.monotonic() < flush_at):
                self._cond.wait(timeout=max(0.0,
                                            flush_at - time.monotonic()))
            if self._gen != gen:
                return None
            now = time.monotonic()
            taken, rows = [], 0
            while self._q:
                req = self._q[0]
                if req.deadline < now:
                    # expired in queue: answer 504 and reclaim the rows
                    self._q.popleft()
                    self._q_rows -= req.rows.shape[0]
                    if _resolve(req.future, exception=RequestTimeoutError(
                            "request expired before its batch ran")):
                        self.stats.record_timeout()
                    continue
                if taken and rows + req.rows.shape[0] > self.max_batch:
                    break
                if taken and req.rows.shape[1:] != taken[0].rows.shape[1:]:
                    # row-shape mismatch: stop the batch here (FIFO; the
                    # odd request heads the NEXT batch) — one malformed
                    # request must fail alone, never poison the batch it
                    # happened to share a window with
                    break
                self._q.popleft()
                self._q_rows -= req.rows.shape[0]
                taken.append(req)
                rows += req.rows.shape[0]
            self.stats.set_queue_depth(self._q_rows)
            if taken:
                self._inflight = (gen, taken)
            return taken

    def _clear_inflight(self, gen: int) -> None:
        with self._cond:
            if self._inflight is not None and self._inflight[0] == gen:
                self._inflight = None
                self._cond.notify_all()  # drain() waiters

    def _run(self, gen: int) -> None:
        try:
            self._run_inner(gen)
        except Exception as e:  # noqa: BLE001 — worker loop boundary
            # an uncaught error anywhere outside the per-batch infer
            # try/except (queue bookkeeping, stats, concatenate) used to
            # kill the worker SILENTLY: every queued request then waited
            # out its full 504 budget and every later submit queued onto
            # a corpse. Fail everything now and mark the batcher dead so
            # submit() fast-fails (WorkerDeadError).
            self._worker_died(gen, e)

    def _worker_died(self, gen: int, exc: Exception) -> None:
        with self._cond:
            if self._gen != gen or not self._running:
                return  # a fenced zombie's death is not news
            self._dead = f"{type(exc).__name__}: {exc}"
            inflight = self._inflight
            self._inflight = None
            queued = list(self._q)
            self._q.clear()
            self._q_rows = 0
            self.stats.set_queue_depth(0)
            self._cond.notify_all()
        self.stats.record_worker_death()
        err = WorkerDeadError(f"batcher worker died: {self._dead}")
        victims = list(inflight[1]) if inflight is not None else []
        victims.extend(queued)
        for req in victims:
            _resolve(req.future, exception=err)
        if self._on_outcome is not None:
            self._on_outcome(False, err)

    def _wedge_handler(self, meta: dict) -> None:
        """Watchdog verdict (runs on the WATCHDOG thread — the wedged
        worker is, by definition, not coming back to run anything): fail
        the in-flight futures with a diagnosis, fence the wedged worker
        out behind a generation bump, start a replacement, and report
        upward (the engine trips the model's breaker and journals
        serve.wedged there)."""
        gen = meta["gen"]
        with self._cond:
            if not self._running or self._gen != gen:
                return  # stop()/an earlier wedge already superseded this
            if self._inflight is None or self._inflight[0] != gen:
                return  # completed inside the race window — not wedged
            taken = self._inflight[1]
            self._inflight = None
            self._gen += 1
            self._cond.notify_all()
        self.stats.record_wedged()
        err = ModelWedgedError(
            f"inference dispatch exceeded the "
            f"{self.watchdog.timeout_s:.2f}s watchdog deadline with "
            f"{meta['rows']} rows in flight — the hung-device signature "
            "(~0 CPU, no error); worker replaced")
        # report upward BEFORE resolving the futures: the engine trips
        # the model's breaker in this hook, and a client unblocked by its
        # failed future can retry within MICROSECONDS — tripping after
        # the resolve would let that retry slip through the pre-trip
        # window and (if it succeeds on the replacement worker) leave
        # the breaker permanently open behind a served request
        if self._on_wedged is not None:
            try:
                self._on_wedged({
                    "rows": int(meta["rows"]),
                    "failed_requests": len(taken),
                    "watchdog_s": self.watchdog.timeout_s,
                    "error": str(err),
                })
            except Exception:  # noqa: BLE001 — reporting never re-wedges
                pass
        for req in taken:
            _resolve(req.future, exception=err)
        with self._cond:
            if self._running:
                self._worker = self._spawn_worker()
                self.stats.record_watchdog_restart()

    def _run_inner(self, gen: int) -> None:
        while True:
            taken = self._take_batch(gen)
            if taken is None:
                return
            if not taken:
                continue  # everything in the window had expired
            try:
                # batch PREP failures (a concatenate the _take_batch
                # shape guard somehow let through) fail this batch's
                # futures only — they must not take the death path and
                # turn one bad window into a permanent batcher outage
                batch = (taken[0].rows if len(taken) == 1
                         else np.concatenate([r.rows for r in taken],
                                             axis=0))
            except Exception as e:  # noqa: BLE001 — batch-prep boundary
                for req in taken:
                    _resolve(req.future, exception=e)
                self._clear_inflight(gen)
                continue
            n = batch.shape[0]
            # fill telemetry mirrors the model's own bucketing decision
            # (ops/dispatch.inference_bucket): pad rows exist only when
            # bucketing is on and n is not already a bucket size
            padded_to = (n if dispatch.bucketing_mode() == "off"
                         else max(dispatch.bucket_size(n), n))
            self.stats.record_batch(n, padded_to)
            wd = self.watchdog
            token = (wd.arm({"gen": gen, "rows": n}) if wd is not None
                     else None)
            try:
                # the coalesced-batch span: carries every member request
                # id, and (running on this worker thread) becomes the
                # PARENT of the dispatch.<jit> span the model call opens
                # — request -> batch -> jit, one joined timeline.
                # Completion is fenced by the infer fn's np.asarray host
                # readback (data-dependent device->host copy), which is
                # also what disarms the watchdog below.
                with obs_trace.span(
                        "serve.batch", rows=int(n),
                        padded_to=int(padded_to),
                        request_ids=[r.rid for r in taken]):
                    out = np.asarray(self._infer(batch))
            except Exception as e:  # noqa: BLE001 — serving boundary
                live = wd.disarm(token) if wd is not None else True
                # per-request error accounting happens at the boundary
                # that answers the client (engine handler / predict
                # caller) — recording here too would double-count; the
                # OUTCOME hook is per-dispatch and feeds the breaker
                for req in taken:
                    _resolve(req.future, exception=e)
                self._clear_inflight(gen)
                if not live:
                    return  # the watchdog already replaced this worker
                if self._on_outcome is not None:
                    self._on_outcome(False, e)
                continue
            live = wd.disarm(token) if wd is not None else True
            if live and self._on_outcome is not None:
                self._on_outcome(True, None)
            i = 0
            for req in taken:
                k = req.rows.shape[0]
                if _resolve(req.future, result=out[i:i + k]):
                    self.stats.record_latency(time.monotonic() - req.enqueued)
                i += k
            self._clear_inflight(gen)
            if not live:
                return  # fenced: the replacement owns the queue now
