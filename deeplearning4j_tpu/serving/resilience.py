"""Serving resilience plane: per-model circuit breakers + hung-inference
watchdog — the serving twin of resilience/trainer.py's training-side story.

The training runtime survives preemption, transient device errors and
corrupt checkpoints (resilience/, PR 3) and the fleet survives worker loss
(parallel/fleet.py, PR 6), but the ServingEngine inherited the reference
route's failure semantics: none (DL4jServeRouteBuilder.java has no health
model at all). The concrete failure modes this module closes:

  * the wedge — a hung device call with ~0 CPU and NO error. The single
    DynamicBatcher worker
    thread blocks forever inside ``infer_fn``; every queued request then
    rots to its 504 with no diagnosis and the engine never recovers.
  * a flaky model — inference raising per batch. Requests keep piling
    onto a doomed queue, each paying full queue latency before failing.
  * a bad rollout — registry load/warmup raising. The exception used to
    propagate to the caller with no per-model record of the failure.

Two mechanisms, composed by the engine:

:class:`CircuitBreaker` — per-model health state machine
    SERVING -> DEGRADED (failures observed, still admitting) -> BROKEN
    (fast-fail: new requests raise :class:`BreakerOpenError`, which the
    HTTP layer answers 503 + Retry-After instead of queueing onto a
    doomed worker). Opened by EITHER ``fails`` consecutive failures or a
    windowed failure rate (``rate`` over the last ``window_s`` seconds,
    once ``min_window`` outcomes exist). After ``cooldown_s`` the breaker
    goes half-open: exactly ONE probe request is admitted; its success
    closes the breaker (back to SERVING), its failure re-opens with a
    fresh cooldown. ``trip()`` force-opens (the watchdog's verdict and
    load/warmup failures land here).

:class:`InferenceWatchdog` — a monitor thread over armed deadlines.
    The batcher arms ``(token, deadline)`` before every dispatch and
    disarms on completion; completion is fenced by the host readback the
    infer fn already performs (``np.asarray`` of the outputs — a
    data-dependent device->host copy). On expiry the watchdog fires ``on_wedged(meta)`` exactly
    once for that token: the batcher fails the in-flight futures with
    :class:`ModelWedgedError` (a diagnosis, not a 504-by-rot), abandons
    the wedged worker thread (generation-fenced: its late completion
    resolves nothing) and starts a replacement, and the engine trips the
    model's breaker and journals a ``serve.wedged`` flight-recorder event
    — so a hung device call degrades one model instead of killing the
    engine.

Env knobs (read by the ENGINE at construction; this module only provides
the parsed defaults):

  DL4J_TPU_SERVE_BREAKER_FAILS  consecutive failures that open a model's
                                breaker (default 5; 0 disables breakers)
  DL4J_TPU_SERVE_WATCHDOG_S     in-flight dispatch wall deadline
                                (default 30.0; 0 disables the watchdog)
  DL4J_TPU_SERVE_DRAIN_S        graceful-drain deadline on stop()/SIGTERM
                                (default 20.0)

Every transition is counted in the ``serving_stats`` ledger
(serving/telemetry.py), which the engine registers in the central
MetricsRegistry (PR 7 convention) — breaker/watchdog/drain counters ride
the same Prometheus scrape as everything else. Fault injection for all of
these paths is config-driven and never ambient:
resilience/chaos.ServingChaosConfig.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional

from deeplearning4j_tpu.ops import env as envknob

ENV_BREAKER_FAILS = "DL4J_TPU_SERVE_BREAKER_FAILS"
ENV_WATCHDOG_S = "DL4J_TPU_SERVE_WATCHDOG_S"
ENV_DRAIN_S = "DL4J_TPU_SERVE_DRAIN_S"

# health states, in degradation order
SERVING = "serving"
DEGRADED = "degraded"
BROKEN = "broken"


def _env_float(name: str, default: float) -> float:
    return envknob.get_float(name, default)


def breaker_fails_default() -> int:
    return int(_env_float(ENV_BREAKER_FAILS, 5))


def watchdog_s_default() -> float:
    return _env_float(ENV_WATCHDOG_S, 30.0)


def drain_s_default() -> float:
    return _env_float(ENV_DRAIN_S, 20.0)


class BreakerOpenError(RuntimeError):
    """The model's circuit breaker is open: fast-fail instead of queueing
    onto a doomed worker. The HTTP layer answers 503 with a Retry-After
    header of :attr:`retry_after_s` seconds."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = max(0.0, float(retry_after_s))


class DrainingError(RuntimeError):
    """The engine is draining (stop()/SIGTERM): admission is closed. The
    HTTP layer answers 503 + Retry-After so a load balancer routes away
    while in-flight requests complete."""

    retry_after_s = 1.0


class ModelWedgedError(RuntimeError):
    """The watchdog expired an in-flight dispatch: the device call hung
    past its wall deadline (the hung-device signature — ~0 CPU, no
    error). Carried to every future the wedged batch held, so clients
    get a diagnosis instead of rotting to a generic queue timeout."""


class ClientRequestError(ValueError):
    """An input-shaping failure raised BEFORE the model dispatch (wrong
    row width, normalizer shape mismatch, wrong endpoint for the model
    type): 400-class CLIENT evidence. The engine answers it like any
    payload error but excludes it from the breaker vote — a malformed
    client must never walk a healthy model to BROKEN and 503 everyone
    else."""


class WorkerDeadError(RuntimeError):
    """The batcher's worker thread is dead and was not replaced: submit
    fast-fails instead of queueing requests nobody will ever serve."""


class CircuitBreaker:
    """Per-model health state machine (see module docstring).

    Thread-safe; the engine holds one per ModelRecord key. Transitions
    fan out to ``stats`` (serving/telemetry.ServingStats counters) and
    the optional ``on_transition(old, new, reason)`` hook (the engine
    journals flight-recorder events there).
    """

    def __init__(self, *, fails: Optional[int] = None,
                 cooldown_s: float = 2.0,
                 window_s: float = 30.0, rate: float = 0.5,
                 min_window: int = 10,
                 probe_ttl_s: float = 60.0,
                 key: str = "", stats=None,
                 on_transition: Optional[Callable[[str, str, str],
                                                  None]] = None) -> None:
        self.fails = int(fails if fails is not None
                         else breaker_fails_default())
        self.cooldown_s = float(cooldown_s)
        self.window_s = float(window_s)
        self.rate = float(rate)
        self.min_window = int(min_window)
        # a probe that never reaches a dispatch outcome (shed at submit,
        # expired in queue, payload error before the model call) must
        # not hold the half-open slot forever: past this TTL a new probe
        # is granted. Default matches the serve request deadline — a
        # probe older than that cannot still be honestly in flight.
        self.probe_ttl_s = float(probe_ttl_s)
        self.key = key
        self.stats = stats
        self.on_transition = on_transition
        self._lock = threading.Lock()
        self._state = SERVING
        self._consecutive = 0
        self._opened_at = 0.0
        self._probing = False
        self._probe_started = 0.0
        self._outcomes: deque = deque()  # (monotonic, ok) rate window
        self.open_reason = ""

    # -- state ------------------------------------------------------------
    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def _set_state(self, new: str, reason: str):
        """Caller holds the lock; returns the (old, new, reason) triple
        for the caller to emit AFTER releasing it — counters and the
        transition hook (which journals) must not run under this lock."""
        old, self._state = self._state, new
        return None if old == new else (old, new, reason)

    def _emit(self, transition) -> None:
        if transition is None:
            return
        old, new, reason = transition
        if self.stats is not None:
            if new == BROKEN:
                self.stats.record_breaker_open()
            elif old == BROKEN and new == SERVING:
                self.stats.record_breaker_close()
        if self.on_transition is not None:
            self.on_transition(old, new, reason)

    # -- admission --------------------------------------------------------
    def check(self) -> bool:
        """Admission gate, called per request BEFORE it enqueues. Returns
        True when the admitted request is the half-open PROBE (its
        outcome decides close-vs-reopen); raises
        :class:`BreakerOpenError` when the breaker is open and it is not
        probe time (or a probe is already in flight)."""
        if self.fails <= 0:  # breakers disabled
            return False
        with self._lock:
            if self._state != BROKEN:
                return False
            now = time.monotonic()
            waited = now - self._opened_at
            probe_free = (not self._probing
                          or now - self._probe_started > self.probe_ttl_s)
            if waited >= self.cooldown_s and probe_free:
                # half-open: exactly one probe rides through; everyone
                # else keeps fast-failing until its verdict is in. A
                # probe with no verdict past its TTL (it was shed at
                # submit, expired in queue, or died before the dispatch
                # outcome hook) forfeits the slot — otherwise the
                # breaker would stay open FOREVER behind a ghost probe.
                self._probing = True
                self._probe_started = now
                if self.stats is not None:
                    self.stats.record_breaker_probe()
                return True
            retry = max(self.cooldown_s - waited, 0.05)
            reason = self.open_reason
        if self.stats is not None:
            self.stats.record_fast_fail()
        raise BreakerOpenError(
            f"model {self.key or '<default>'} breaker open"
            f" ({reason}); retry after {retry:.2f}s",
            retry_after_s=retry)

    # -- outcomes ---------------------------------------------------------
    def record_success(self) -> None:
        if self.fails <= 0:  # disabled: no state tracking at all
            return
        transition = None
        with self._lock:
            self._consecutive = 0
            self._push_outcome(True)
            if self._state == DEGRADED:
                transition = self._set_state(SERVING, "recovered")
            elif self._state == BROKEN and self._probing:
                self._probing = False
                self._outcomes.clear()
                transition = self._set_state(SERVING, "probe succeeded")
        self._emit(transition)

    def record_failure(self, reason: str = "inference error") -> None:
        if self.fails <= 0:
            # disabled means DISABLED: a vote-counting path that still
            # flipped state would mark a serving model broken in /health
            # with no probe path back (check() never grants one)
            return
        transition = None
        with self._lock:
            self._consecutive += 1
            self._push_outcome(False)
            if self._state == BROKEN:
                if self._probing:
                    # attributed to the probe. APPROXIMATE on the
                    # batched path: outcomes arrive per coalesced
                    # DISPATCH without request identity, so a pre-open
                    # straggler failing during the probe window re-opens
                    # early and the real probe's later success is
                    # dropped. Bounded damage: recovery slips one
                    # cooldown cycle (probe_ttl_s guarantees another
                    # probe); precise attribution would need request
                    # identity threaded through shared batch outcomes.
                    self._probing = False
                    self._opened_at = time.monotonic()
                    self.open_reason = f"probe failed: {reason}"
            elif self._consecutive >= self.fails:
                transition = self._open(
                    f"{self._consecutive} consecutive failures: {reason}")
            elif self._window_tripped():
                transition = self._open(
                    f"failure rate over {self.window_s:.0f}s window >= "
                    f"{self.rate:.0%}: {reason}")
            elif self._state == SERVING:
                transition = self._set_state(DEGRADED, reason)
        self._emit(transition)

    def trip(self, reason: str) -> None:
        """Force-open (watchdog verdict, load/warmup failure): no vote
        counting — the evidence is categorical."""
        if self.fails <= 0:
            return
        with self._lock:
            self._probing = False
            transition = self._open(reason)
        self._emit(transition)

    # -- internals (caller holds the lock) --------------------------------
    def _open(self, reason: str):
        self._opened_at = time.monotonic()
        self.open_reason = reason
        return self._set_state(BROKEN, reason)

    def _push_outcome(self, ok: bool) -> None:
        now = time.monotonic()
        self._outcomes.append((now, ok))
        horizon = now - self.window_s
        while self._outcomes and self._outcomes[0][0] < horizon:
            self._outcomes.popleft()

    def _window_tripped(self) -> bool:
        if len(self._outcomes) < self.min_window:
            return False
        bad = sum(1 for _, ok in self._outcomes if not ok)
        return bad / len(self._outcomes) >= self.rate

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"state": self._state,
                    "consecutive_failures": self._consecutive,
                    "open_reason": self.open_reason if
                    self._state == BROKEN else ""}


class InferenceWatchdog:
    """Monitor thread over armed in-flight deadlines.

    ``arm(meta, deadline)`` returns a token; ``disarm(token)`` on
    completion. A token whose deadline passes without a disarm gets ONE
    ``on_wedged(meta)`` callback on the watchdog thread (never on the
    wedged thread — it is, by definition, not coming back). The
    arm/disarm pair brackets the batcher's ``infer_fn`` call, whose
    trailing ``np.asarray`` host readback is the completion fence.

    The monitor wakes at the nearest armed deadline (or idles on the
    condition) — no fixed-rate polling.
    """

    def __init__(self, timeout_s: float,
                 on_wedged: Callable[[Any], None],
                 name: str = "inference-watchdog") -> None:
        self.timeout_s = float(timeout_s)
        self.on_wedged = on_wedged
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._armed: Dict[int, tuple] = {}  # token -> (deadline, meta)
        self._next_token = 1
        self._running = True
        self.fired = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    @property
    def enabled(self) -> bool:
        return self.timeout_s > 0

    def arm(self, meta: Any = None,
            timeout_s: Optional[float] = None) -> Optional[int]:
        if not self.enabled:
            return None
        budget = timeout_s if timeout_s is not None else self.timeout_s
        with self._cond:
            token = self._next_token
            self._next_token += 1
            self._armed[token] = (time.monotonic() + budget, meta)
            self._cond.notify_all()
        return token

    def disarm(self, token: Optional[int]) -> bool:
        """True when the token was still armed (the dispatch completed
        before the watchdog fired); False when the watchdog already
        declared it wedged — the caller's late completion is fenced."""
        if token is None:
            return True
        with self._cond:
            return self._armed.pop(token, None) is not None

    def stop(self) -> None:
        with self._cond:
            self._running = False
            self._armed.clear()
            self._cond.notify_all()
        self._thread.join(timeout=5)

    def _run(self) -> None:
        while True:
            with self._cond:
                if not self._running:
                    return
                if not self._armed:
                    self._cond.wait()
                    continue
                now = time.monotonic()
                expired = [(tok, meta) for tok, (dl, meta)
                           in self._armed.items() if dl <= now]
                for tok, _ in expired:
                    del self._armed[tok]
                if not expired:
                    nearest = min(dl for dl, _ in self._armed.values())
                    self._cond.wait(timeout=max(0.005, nearest - now))
                    continue
                self.fired += len(expired)
            for _, meta in expired:
                try:
                    self.on_wedged(meta)
                except Exception:  # noqa: BLE001 — the monitor must survive its handler
                    pass
