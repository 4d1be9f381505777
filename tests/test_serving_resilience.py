"""Serving resilience plane tests (ISSUE 8): chaos-driven degradation
contracts for the circuit breaker, the hung-inference watchdog, graceful
drain, registry failure isolation and decode-slot crash eviction.

The training side proved interrupted==uninterrupted under injected faults
(tests/test_resilience.py, PR 3) and the fleet proved loss==replay
(tests/test_fleet.py, PR 6); this file is the serving third of that
convention: every failure path is provoked DETERMINISTICALLY through
resilience/chaos.ServingChaosConfig (never ambient — an engine without a
configured chaos object is byte-identical to one built before the plane
existed, which the equivalence test here locks) and every recovery claim
is asserted end-to-end: the engine serves fresh traffic again after the
injected wedge, the prior model version keeps serving through a failed
rollout, co-resident decode slots survive a crashed admission.

Reference anchor: the route being hardened had NO failure semantics at
all (dl4j-streaming/.../routes/DL4jServeRouteBuilder.java — one static
model, exceptions propagate, health is implicit) — every contract here is
beyond-reference, motivated by the wedge: a hung device call with ~0 CPU
and NO error.
"""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience import (
    InjectedServingFault,
    ServingChaos,
    ServingChaosConfig,
)
from deeplearning4j_tpu.serving import (
    BreakerOpenError,
    CircuitBreaker,
    DynamicBatcher,
    ServingEngine,
    ServingStats,
    WorkerDeadError,
)
from deeplearning4j_tpu.serving.resilience import BROKEN, DEGRADED, SERVING

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_net(seed=7, n_in=4, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.1)
            .list()
            .layer(0, DenseLayer(n_in=n_in, n_out=8, activation="tanh"))
            .layer(1, OutputLayer(n_in=8, n_out=n_out, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    net.fit(rng.normal(size=(32, n_in)).astype(np.float32),
            np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, 32)])
    return net


def _post(url, path, payload, timeout=60):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(url, path, timeout=30):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return json.loads(r.read())


def _code_of(fn, *a, **kw):
    """(status_code, body_dict, headers) of an HTTP call that may error."""
    try:
        return 200, fn(*a, **kw), {}
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


@pytest.fixture
def obs_on():
    obs.set_enabled(True)
    obs.tracer().clear()
    try:
        yield
    finally:
        obs.set_enabled(None)


# ---------------------------------------------------------------------------
# CircuitBreaker state machine
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_serving_degraded_broken_walk(self):
        st = ServingStats()
        br = CircuitBreaker(fails=3, cooldown_s=60, stats=st, key="m@v1")
        assert br.state == SERVING
        br.record_failure("boom")
        assert br.state == DEGRADED  # failing but still admitting
        assert br.check() is False   # not a probe, not a shed
        br.record_success()
        assert br.state == SERVING   # one success heals DEGRADED
        for _ in range(3):
            br.record_failure("boom")
        assert br.state == BROKEN
        assert st.breaker_opens == 1
        with pytest.raises(BreakerOpenError) as ei:
            br.check()
        assert ei.value.retry_after_s > 0
        assert st.fast_fails_503 == 1

    def test_half_open_probe_close_and_reopen(self):
        st = ServingStats()
        br = CircuitBreaker(fails=2, cooldown_s=0.15, stats=st)
        br.record_failure("a")
        br.record_failure("a")
        assert br.state == BROKEN
        time.sleep(0.2)
        assert br.check() is True        # THE half-open probe
        with pytest.raises(BreakerOpenError):
            br.check()                   # co-requests shed until verdict
        br.record_failure("probe died")  # probe fails -> re-open
        assert br.state == BROKEN
        time.sleep(0.2)
        assert br.check() is True
        br.record_success()              # probe succeeds -> close
        assert br.state == SERVING
        assert br.check() is False
        assert st.breaker_probes == 2 and st.breaker_closes == 1

    def test_rate_window_opens_without_consecutive_run(self):
        """Alternating ok/fail never reaches `fails` consecutive, but the
        windowed failure rate crosses 0.5 once enough outcomes exist."""
        br = CircuitBreaker(fails=100, window_s=60, rate=0.5, min_window=8)
        for _ in range(5):
            br.record_success()
            br.record_failure("flaky")
        assert br.state == BROKEN
        assert "rate" in br.open_reason

    def test_trip_is_categorical(self):
        st = ServingStats()
        br = CircuitBreaker(fails=5, stats=st)
        br.trip("watchdog: wedged")
        assert br.state == BROKEN and st.breaker_opens == 1
        br.trip("again")  # re-trip: fresh cooldown, no double count
        assert st.breaker_opens == 1

    def test_disabled_breaker_never_sheds_and_never_breaks(self):
        """fails=0 means DISABLED end to end: no shedding AND no state
        tracking — a vote path that still flipped BROKEN would 503 the
        /health of a model that keeps serving fine, with no probe path
        back (check() never grants one when disabled)."""
        st = ServingStats()
        br = CircuitBreaker(fails=0, stats=st)
        for _ in range(20):
            br.record_failure("x")
        br.trip("categorical-looking evidence")
        assert br.check() is False
        assert br.state == SERVING
        assert st.breaker_opens == 0

    def test_ghost_probe_forfeits_slot_after_ttl(self):
        """A probe that never reaches a dispatch outcome (shed at
        submit, expired in queue, payload error) must not hold the
        half-open slot forever — past probe_ttl_s a NEW probe is
        granted, so the breaker cannot stay open behind a ghost."""
        br = CircuitBreaker(fails=1, cooldown_s=0.05, probe_ttl_s=0.15)
        br.record_failure("x")
        assert br.state == BROKEN
        time.sleep(0.06)
        assert br.check() is True   # probe granted...
        with pytest.raises(BreakerOpenError):
            br.check()              # ...slot held while fresh
        time.sleep(0.2)             # the probe never reported back
        assert br.check() is True   # TTL expired: slot forfeited, re-probe
        br.record_success()
        assert br.state == SERVING


# ---------------------------------------------------------------------------
# breaker over HTTP: chaos infer-raise walks the model to BROKEN and back
# ---------------------------------------------------------------------------


class TestBreakerHTTP:
    def test_consecutive_failures_503_then_probe_recovers(self):
        chaos = ServingChaos(ServingChaosConfig(infer_raise_at=1,
                                                infer_raise_count=3))
        eng = ServingEngine(model=small_net(), max_wait_ms=5,
                            breaker_fails=3, breaker_cooldown_s=0.3,
                            chaos=chaos).start()
        try:
            codes = []
            for _ in range(5):
                code, body, headers = _code_of(
                    _post, eng.url, "/predict",
                    {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
                codes.append(code)
                if code == 503:
                    # the shed contract: Retry-After rides the 503 so a
                    # client backs off instead of hammering the breaker;
                    # RFC 9110 delta-seconds — an INTEGER >= 1, or
                    # standard retry parsers silently drop it
                    assert int(headers["Retry-After"]) >= 1
            # three injected failures (400 each), then the OPEN breaker
            # fast-fails everything else without touching the model
            assert codes == [400, 400, 400, 503, 503]
            assert len(chaos.log) == 3  # the breaker shed, chaos untouched
            m = eng.metrics()
            assert m["serving"]["breaker_opens"] == 1
            assert m["serving"]["fast_fails_503"] >= 2
            assert m["health"]["default@v1"] == "broken"
            # cooldown passes -> the next request IS the half-open probe;
            # chaos is exhausted so it succeeds and closes the breaker
            time.sleep(0.35)
            out = _post(eng.url, "/predict",
                        {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert len(out["output"]) == 3
            m = eng.metrics()
            assert m["serving"]["breaker_closes"] == 1
            assert m["health"]["default@v1"] == "serving"
        finally:
            eng.stop()


    def test_client_payload_errors_never_open_the_breaker(self):
        """400-class evidence stays 400-class: a stream of malformed
        requests (wrong row width -> reshape fails BEFORE the model
        call) must not walk a healthy model to BROKEN and 503 everyone
        else."""
        eng = ServingEngine(model=small_net(), input_shape=(4,),
                            max_wait_ms=5, breaker_fails=3).start()
        try:
            for _ in range(6):  # twice the breaker threshold
                code, _, _ = _code_of(_post, eng.url, "/predict",
                                      {"record": [0.1, 0.2]}, 30)  # width 2
                assert code == 400
            # the model is still healthy and still serving
            out = _post(eng.url, "/predict",
                        {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert len(out["output"]) == 3
            m = eng.metrics()
            assert m["serving"]["breaker_opens"] == 0
            assert m["health"]["default@v1"] == "serving"
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# hung-inference watchdog: the wedge, detected and survived
# ---------------------------------------------------------------------------


class TestWatchdog:
    def test_injected_hang_diagnosed_journaled_recovered(self, obs_on):
        """The acceptance headline: an injected infer-hang (the
        hung-device signature — blocks, ~0 CPU, no error) is detected within
        the watchdog deadline, pending requests fail with a DIAGNOSIS
        (well before their 504 budget — not 504-by-rot), serve.wedged is
        journaled, and the engine serves fresh traffic again."""
        # the hang injects at dispatch 2: dispatch 1 warms the jit trace
        # first, so the watchdog deadline is judged against a steady-state
        # dispatch — a first-dispatch trace under full quick-gate load on
        # this 1-core host can legitimately exceed a sub-second deadline
        chaos = ServingChaos(ServingChaosConfig(infer_hang_at=2,
                                                infer_hang_s=30.0))
        eng = ServingEngine(model=small_net(), max_wait_ms=5,
                            watchdog_s=0.8, breaker_fails=3,
                            breaker_cooldown_s=0.3, chaos=chaos).start()
        try:
            warm = _post(eng.url, "/predict",
                         {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert len(warm["output"]) == 3
            t0 = time.monotonic()
            code, body, _ = _code_of(
                _post, eng.url, "/predict",
                {"record": [0.1, 0.2, 0.3, 0.4], "timeout_s": 30}, 40)
            detect_s = time.monotonic() - t0
            assert code == 503
            assert "Wedged" in body["error"]          # the diagnosis...
            assert "watchdog" in body["error"]
            assert detect_s < 5.0                     # ...not 30s of rot
            m = eng.metrics()["serving"]
            assert m["wedged_batches"] == 1
            assert m["watchdog_restarts"] == 1
            # the flight recorder holds the wedge event (post-mortem
            # evidence even if the process dies next — it was fsync'd)
            wedged = obs.default_journal().events("serve.wedged")
            assert wedged and wedged[-1]["model"] == "default@v1"
            assert wedged[-1]["failed_requests"] == 1
            # the wedge tripped the breaker: immediate requests shed 503
            code, _, _ = _code_of(_post, eng.url, "/predict",
                                  {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert code == 503
            # cooldown passes; the probe rides the REPLACED worker (the
            # wedged thread is fenced out) and closes the breaker: the
            # engine is serving again with a live-but-abandoned hang
            # still pending inside the old thread
            time.sleep(0.35)
            out = _post(eng.url, "/predict",
                        {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert len(out["output"]) == 3
            assert eng.metrics()["health"]["default@v1"] == "serving"
        finally:
            chaos.release_hangs()  # unblock the abandoned worker thread
            eng.stop()

    def test_fast_traffic_never_false_positives(self):
        net = small_net()
        eng = ServingEngine(model=net, max_wait_ms=5, watchdog_s=5.0).start()
        try:
            rng = np.random.default_rng(3)
            rows = rng.normal(size=(8, 4)).astype(np.float32)
            with ThreadPoolExecutor(max_workers=8) as ex:
                list(ex.map(
                    lambda i: _post(eng.url, "/predict",
                                    {"record": rows[i].tolist()}, 30),
                    range(8)))
            m = eng.metrics()["serving"]
            assert m["wedged_batches"] == 0
            assert m["watchdog_restarts"] == 0
            assert m["completed"] == 8
        finally:
            eng.stop()

    def test_slow_infer_is_degradation_not_wedge(self):
        """A dispatch slower than typical but inside the deadline must
        complete normally — the watchdog keys on the DEADLINE, not on
        'slower than usual' heuristics."""
        chaos = ServingChaos(ServingChaosConfig(slow_infer_at=1,
                                                slow_infer_s=0.3))
        eng = ServingEngine(model=small_net(), max_wait_ms=5,
                            watchdog_s=5.0, chaos=chaos).start()
        try:
            out = _post(eng.url, "/predict",
                        {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert len(out["output"]) == 3
            assert eng.metrics()["serving"]["wedged_batches"] == 0
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# dead worker: fast-fail at submit, no abandoned futures at stop
# ---------------------------------------------------------------------------


class TestDeadWorker:
    def test_submit_fast_fails_after_worker_death(self):
        class Dying(DynamicBatcher):
            def _take_batch(self, gen):
                raise RuntimeError("worker loop bug")

        b = Dying(lambda x: np.asarray(x), max_batch=4, max_wait_ms=1)
        try:
            deadline = time.monotonic() + 5
            while b._dead is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert b._dead is not None
            # the satellite fix: submit checks liveness and fast-fails
            # instead of queueing onto a corpse until the 504
            with pytest.raises(WorkerDeadError):
                b.submit(np.zeros((1, 2), np.float32))
            assert b.stats.worker_deaths == 1
        finally:
            b.stop()

    def test_worker_death_fails_queued_futures(self):
        """Requests already queued when the worker dies get the REAL
        cause immediately, not a silent wait to 504."""
        gate = threading.Event()
        state = {"n": 0}

        def infer(x):
            state["n"] += 1
            if state["n"] == 1:
                gate.wait(timeout=10)  # hold batch 1 while queue builds
                return np.asarray(x)
            raise BaseException("out-of-band")  # noqa: TRY002 — unreachable

        b = DynamicBatcher(infer, max_batch=1, max_wait_ms=1)
        try:
            f1 = b.submit(np.zeros((1, 2), np.float32))
            f2 = b.submit(np.zeros((1, 2), np.float32))  # queued
            # kill the worker loop out from under the queue: the next
            # _take_batch call raises (simulates a loop bug, the same
            # class the Dying subclass hits at birth)
            b._take_batch = None  # TypeError on next call -> worker dies
            gate.set()
            np.testing.assert_array_equal(f1.result(timeout=10),
                                          np.zeros((1, 2), np.float32))
            with pytest.raises(WorkerDeadError):
                f2.result(timeout=10)
        finally:
            gate.set()
            b.stop()

    def test_stop_fails_inflight_futures(self):
        """stop() must fail — never abandon — the batch the worker holds
        INSIDE infer_fn: those futures are not in the queue, and the old
        stop() walked only the queue."""
        hold = threading.Event()

        def infer(x):
            hold.wait(timeout=30)
            return np.asarray(x)

        b = DynamicBatcher(infer, max_batch=2, max_wait_ms=1)
        try:
            f = b.submit(np.zeros((1, 2), np.float32))
            deadline = time.monotonic() + 5
            while b._inflight is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert b._inflight is not None
            b.stop(timeout_s=0.2)  # worker is stuck; do not wait 5s
            with pytest.raises(RuntimeError, match="in flight"):
                f.result(timeout=5)
        finally:
            hold.set()


# ---------------------------------------------------------------------------
# graceful drain: stop()/SIGTERM answers everything admitted
# ---------------------------------------------------------------------------


class TestDrain:
    def test_drain_under_load_completes_every_admitted_request(self):
        net = small_net()

        class SlowNet:
            def output(self, x):
                time.sleep(0.05)  # stretch the dispatch so a queue forms
                return net.output(x)

        eng = ServingEngine(model=SlowNet(), max_batch=2, max_wait_ms=1,
                            drain_s=20.0).start()
        try:
            with ThreadPoolExecutor(max_workers=8) as ex:
                futs = [ex.submit(_post, eng.url, "/predict",
                                  {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
                        for _ in range(8)]
                time.sleep(0.08)  # some in flight, some queued
                t0 = time.monotonic()
                ok = eng.drain()
                drain_s = time.monotonic() - t0
                # every ADMITTED request completed with a real answer
                for f in futs:
                    assert len(f.result()["output"]) == 3
            assert ok and drain_s < 15.0
            m = eng.metrics()["serving"]
            assert m["drains_started"] == 1 and m["drains_completed"] == 1
            # admission is closed: new traffic sheds 503 + Retry-After
            code, _, headers = _code_of(
                _post, eng.url, "/predict",
                {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert code == 503 and "Retry-After" in headers
            code, body, _ = _code_of(_get, eng.url, "/health")
            assert code == 503 and body["draining"]
        finally:
            eng.stop(drain=False)

    def test_sigterm_stops_admission_and_drains(self, obs_on):
        """The preemption path, wired like ResilientTrainer's
        checkpoint-before-death: a REAL SIGTERM closes admission in the
        handler, drains on a worker thread, journals the preempt marker
        and flushes the journal."""
        import signal as _signal

        prev_handler = _signal.getsignal(_signal.SIGTERM)
        eng = ServingEngine(model=small_net(), max_wait_ms=5,
                            handle_signals=True).start()
        try:
            _post(eng.url, "/predict", {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            os.kill(os.getpid(), _signal.SIGTERM)
            deadline = time.monotonic() + 10
            while not eng._draining and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng._draining
            # the drain thread finishes shutdown; the journal holds the
            # preempt marker + drain completion
            deadline = time.monotonic() + 10
            while (not obs.default_journal().events("serve.drain_complete")
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert obs.default_journal().events("serve.preempt")
            assert obs.default_journal().events("serve.drain_complete")
        finally:
            eng.stop(drain=False)
        # the engine restored the previous SIGTERM disposition (the
        # drain thread's stop() cannot restore — not the main thread —
        # so this stop() from the test's main thread did)
        assert _signal.getsignal(_signal.SIGTERM) == prev_handler


# ---------------------------------------------------------------------------
# registry failure isolation: a bad rollout never takes down the old model
# ---------------------------------------------------------------------------


class TestRegistryIsolation:
    def test_load_failure_lands_broken_prior_version_keeps_serving(self):
        chaos = ServingChaos(ServingChaosConfig(load_fail_name="v2"))
        eng = ServingEngine(model=small_net(), max_wait_ms=5,
                            chaos=chaos).start()
        try:
            code, body, _ = _code_of(
                _post, eng.url, "/models",
                {"action": "load", "name": "v2", "path": "/nope.zip"}, 30)
            assert code == 400 and "injected load failure" in body["error"]
            # the failed rollout is AUDITABLE, not vanished: a broken
            # record with the error preserved
            models = {f"{d['name']}@v{d['version']}": d
                      for d in _get(eng.url, "/models")["models"]}
            assert models["v2@v1"]["state"] == "broken"
            assert "injected" in models["v2@v1"]["error"]
            # THE contract: the prior serving version is untouched
            out = _post(eng.url, "/predict",
                        {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert len(out["output"]) == 3
            h = _get(eng.url, "/health")
            assert h["ok"] and h["health"]["default@v1"] == "serving"
            assert h["health"]["v2@v1"] == "broken"
            assert eng.metrics()["serving"]["load_failures"] == 1
            # traffic explicitly aimed at the broken record sheds 503
            code, _, _ = _code_of(
                _post, eng.url, "/predict",
                {"record": [0.1] * 4, "model": "v2"}, 30)
            assert code == 503
        finally:
            eng.stop()

    def test_warmup_failure_isolates_and_serve_refuses(self):
        chaos = ServingChaos(ServingChaosConfig(warmup_fail_name="v2"))
        eng = ServingEngine(model=small_net(), max_wait_ms=5,
                            chaos=chaos).start()
        try:
            eng.registry.load("v2", model=small_net(seed=9),
                              input_shape=(4,))
            code, body, _ = _code_of(
                _post, eng.url, "/models",
                {"action": "warmup", "name": "v2", "max_batch": 4}, 30)
            assert code == 400 and "injected warmup failure" in body["error"]
            assert eng.registry.get("v2").state == "broken"
            # a broken record cannot be promoted onto traffic
            with pytest.raises(ValueError, match="refusing to serve"):
                eng.registry.serve("v2")
            assert eng.registry.default().key == "default@v1"
            out = _post(eng.url, "/predict",
                        {"record": [0.1, 0.2, 0.3, 0.4]}, 30)
            assert len(out["output"]) == 3
            assert eng.metrics()["serving"]["warmup_failures"] == 1
        finally:
            eng.stop()

    def test_warmup_rehabilitates_broken_record(self):
        """A record broken at warmup that later warms clean is
        rehabilitated (the operator's re-warm IS the probe)."""
        from deeplearning4j_tpu.serving import ModelRegistry

        net = small_net()
        state = {"fail": True}

        class Flaky:
            def output(self, x):
                if state["fail"]:
                    raise RuntimeError("first warmup dies")
                return net.output(x)

        reg = ModelRegistry()
        reg.load("m", model=Flaky(), input_shape=(4,))
        with pytest.raises(RuntimeError):
            reg.warmup("m", max_batch=2)
        rec = reg.get("m")
        assert rec.state == "broken" and "first warmup" in rec.error
        state["fail"] = False
        reg.warmup("m", max_batch=2)
        assert rec.state == "warm" and rec.error is None


# ---------------------------------------------------------------------------
# decode-slot crash: evicted + failed without poisoning co-residents
# ---------------------------------------------------------------------------


def tiny_lm(**over):
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=32, use_flash=False)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


class TestSlotCrash:
    def test_crashed_admission_preserves_coresident_tokens(self):
        """The slot-independence contract under failure: admission k
        crashes, ONLY its future fails, and a co-resident's greedy
        tokens equal its solo baseline — the crash neither poisons the
        pool nor kills the decoder."""
        from deeplearning4j_tpu.serving.decode import ContinuousDecoder

        lm = tiny_lm()
        # admissions: 1 = solo baseline, 2 = the long co-resident,
        # 3 = the crasher
        chaos = ServingChaos(ServingChaosConfig(admit_raise_at=3))
        d = ContinuousDecoder(lm, slots=2, chaos=chaos)
        try:
            prompt = [1, 5, 2, 9]
            # solo baseline decoded first (admission 1 is clean)
            solo = d.generate(np.asarray([prompt]), 8, temperature=0.0)[0]
            long_fut = d.submit(prompt, 8, temperature=0.0)
            time.sleep(0.05)  # let admission 1 land before the crasher
            crash_fut = d.submit([3, 3, 4], 6, temperature=0.0)
            with pytest.raises(InjectedServingFault):
                crash_fut.result(timeout=60)
            cosched = long_fut.result(timeout=120)
            np.testing.assert_array_equal(solo, cosched)
            assert d.stats.slot_crashes == 1
            # the pool is still alive: a fresh prompt decodes fine
            again = d.generate(np.asarray([prompt]), 8, temperature=0.0)[0]
            np.testing.assert_array_equal(solo, again)
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# equivalence guard: the plane is accounting, never arithmetic
# ---------------------------------------------------------------------------


class TestEquivalence:
    def test_batcher_equals_direct_output_with_plane_armed(self):
        """DL4J_TPU_OBS=0 byte-equivalence (the acceptance criterion):
        with the watchdog armed and breakers live, batcher outputs remain
        byte-identical to direct net.output() — the resilience plane is
        host-side accounting around the dispatch, never inside it."""
        obs.set_enabled(False)
        try:
            net = small_net()
            eng = ServingEngine(model=net, max_wait_ms=60,
                                watchdog_s=10.0, breaker_fails=3).start()
            try:
                rng = np.random.default_rng(11)
                rows = rng.normal(size=(6, 4)).astype(np.float32)
                futs = [eng._batcher_for(eng.registry.default())
                        .submit(rows[i:i + 1]) for i in range(6)]
                got = np.concatenate([f.result(timeout=60) for f in futs])
                direct = np.asarray(net.output(rows))
                np.testing.assert_array_equal(got, direct)
            finally:
                eng.stop()
        finally:
            obs.set_enabled(None)


# ---------------------------------------------------------------------------
# conventions: ledger registration (PR 7) + bench-leg registration
# ---------------------------------------------------------------------------


class TestConventions:
    def test_serving_stats_ledger_carries_resilience_counters(self):
        """The breaker/watchdog/drain counters ride the engine's
        registered serving_stats ledger (the PR 7 registration
        convention) and flatten into the central Prometheus scrape."""
        from deeplearning4j_tpu.obs import registry as obs_registry

        eng = ServingEngine(model=small_net())
        try:
            reg = obs_registry.default_registry()
            assert reg.ledgers(eng).get("serving_stats") is eng.stats
            snap = eng.stats.snapshot()
            for key in ("breaker_opens", "breaker_closes", "fast_fails_503",
                        "wedged_batches", "watchdog_restarts",
                        "worker_deaths", "slot_crashes", "load_failures",
                        "warmup_failures", "drains_started",
                        "drains_completed"):
                assert key in snap, key
            page = reg.render_prometheus()
            assert "dl4j_serving_wedged_batches" in page
            assert "dl4j_serving_breaker_opens" in page
            assert "dl4j_serving_drains_started" in page
        finally:
            eng.stop(drain=False)

    def test_chaos_never_ambient(self):
        """The zero-behavior-change contract: an engine WITHOUT a chaos
        object has no injection hook anywhere on its dispatch path."""
        eng = ServingEngine(model=small_net())
        try:
            assert eng.chaos is None
            assert eng.registry.chaos is None
            out = eng.predict(np.zeros((1, 4), np.float32))
            assert out.shape == (1, 3)
        finally:
            eng.stop()
