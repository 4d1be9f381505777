"""Replicated serving fleet tests (ISSUE 12): router+replicas byte-
identical to a single engine, chaos-killed replica => zero failed
admitted requests, rolling rollout with injected warmup failure never
moves a serving default, fleet-wide SLO shed, replica-breaker ejection +
half-open re-admission, the liveness/readiness split, and the
seal-on-drain rollout/SIGTERM race fix.

The training fleet proved loss==replay (tests/test_fleet.py, PR 6); this
file is the SERVING side of that convention over the same membership
authority (parallel/fleet.FileMembershipBoard). Every fault is provoked
deterministically through resilience/chaos.RouterChaosConfig /
ServingChaosConfig (never ambient).

Reference anchor: the reference's scaleout tree
(deeplearning4j-scaleout spark/akka/zookeeper — SURVEY) never grew a
serving twin; DL4jServeRouteBuilder.java is one process with no failover
— every contract here is beyond-reference.
"""

import json
import os
import re
import time
import urllib.error
import urllib.request

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
    RouterChaos,
    RouterChaosConfig,
    ServingChaos,
    ServingChaosConfig,
)
from deeplearning4j_tpu.serving import DrainingError, ServingEngine
from deeplearning4j_tpu.serving.fleet import ServingFleet
from deeplearning4j_tpu.serving.router import (
    FleetOverloadError,
    FleetRouter,
)
from deeplearning4j_tpu.utils.serialization import ModelSerializer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_net(seed=7, n_in=4, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.1)
            .list()
            .layer(0, DenseLayer(n_in=n_in, n_out=8, activation="tanh"))
            .layer(1, OutputLayer(n_in=8, n_out=n_out, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(seed)
    net.fit(rng.normal(size=(32, n_in)).astype(np.float32),
            np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, 32)])
    return net


@pytest.fixture(scope="module")
def net():
    return small_net()


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(3)
    return rng.normal(size=(16, 4)).astype(np.float32)


def _post_raw(url, path, payload, timeout=60):
    """(status, raw body bytes) — byte-level for the identity contract;
    4xx/5xx answered bodies are returned, not raised."""
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _get(url, path, timeout=30):
    try:
        with urllib.request.urlopen(url + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _fleet(net, n=2, **kw):
    kw.setdefault("heartbeat_s", 0.5)
    return ServingFleet(model=net, replicas=n, **kw).start()


# ---------------------------------------------------------------------------
# byte identity: the acceptance contract
# ---------------------------------------------------------------------------


class TestByteIdentity:
    def test_router_plus_replicas_equals_single_engine(self, net, rows):
        """The same request stream through router+2 replicas and through
        one solo engine must produce BYTE-identical response bodies."""
        solo = ServingEngine(model=net).start()
        fleet = _fleet(net, 2)
        try:
            stream = [rows[:1], rows[1:4], rows[4:9], rows[2:3],
                      rows[:8], rows[9:16]]
            for batch in stream:
                payload = {"batch": batch.tolist()}
                s_code, s_body = _post_raw(solo.url, "/predict", payload)
                f_code, f_body = _post_raw(fleet.url, "/predict", payload)
                assert (s_code, f_code) == (200, 200)
                assert s_body == f_body  # bytes, not parsed floats
        finally:
            fleet.stop()
            solo.stop()


# ---------------------------------------------------------------------------
# chaos kill: zero failed admitted requests
# ---------------------------------------------------------------------------


class TestChaosKill:
    def test_killed_replica_loses_no_admitted_request(self, net, rows):
        """A replica hard-killed mid-stream (RouterChaos verdict, enacted
        through the fleet's kill hook — no drain, no goodbye): every
        /predict in the stream still answers 200 with byte-correct
        output, retried on the survivor."""
        chaos = RouterChaos(RouterChaosConfig(
            kill_replica={"replica": "r0", "after_proxied": 3}))
        # slow the background poll so the REQUEST path (connect failure
        # -> breaker vote -> retry-on-survivor) is the detector — with
        # the default fast poll the readiness probe wins the race and
        # the corpse is skipped before any request touches it
        fleet = _fleet(net, 2, chaos=chaos,
                       router_kwargs={"poll_s": 30.0})
        try:
            expect = np.asarray(net.output(rows[:2]))
            for i in range(20):
                code, body = _post_raw(fleet.url, "/predict",
                                       {"batch": rows[:2].tolist()})
                assert code == 200, f"request {i} failed: {body!r}"
                out = np.asarray(json.loads(body)["outputs"],
                                 np.float32)
                np.testing.assert_array_equal(
                    out, np.asarray(expect, np.float32))
            # the kill really happened and really was detected
            assert any("kill_replica" in str(f) for _, f in chaos.log)
            assert not fleet._handles["r0"].alive
            snap = fleet.router.stats.snapshot()
            assert snap["replica_failures"] >= 1
            assert snap["retries"] >= 1
            # board expiry scrubs the corpse from membership
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                fleet.router.refresh()
                if sorted(fleet.router.describe_replicas()) == ["r1"]:
                    break
                time.sleep(0.1)
            assert sorted(fleet.router.describe_replicas()) == ["r1"]
            code, body = _get(fleet.url, "/health")
            assert code == 200 and body["routable"] == ["r1"]
        finally:
            fleet.stop()

    def test_announced_departure_is_a_clean_leave(self, net, rows):
        fleet = _fleet(net, 2)
        try:
            fleet.depart_replica("r1")
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                fleet.router.refresh()
                if sorted(fleet.router.describe_replicas()) == ["r0"]:
                    break
                time.sleep(0.1)
            assert sorted(fleet.router.describe_replicas()) == ["r0"]
            code, _ = _post_raw(fleet.url, "/predict",
                                {"batch": rows[:2].tolist()})
            assert code == 200
            # a goodbye is not a failure: no breaker activity
            assert fleet.router.stats.snapshot()["breaker_opens"] == 0
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# replica breaker: ejection + half-open re-admission
# ---------------------------------------------------------------------------


class TestReplicaBreaker:
    def test_partition_ejects_then_halfopen_readmits(self, net, rows):
        """A router->replica partition (connect failures, process alive):
        consecutive failures eject the replica; once the partition heals
        the half-open probe re-admits it. The CLIENT sees 200 for every
        request throughout — retried on the survivor."""
        e0 = ServingEngine(model=net).start()
        e1 = ServingEngine(model=net).start()
        chaos = RouterChaos(RouterChaosConfig(
            partition_replica={"replica": "r0", "calls": 2}))
        router = FleetRouter(
            replicas={"r0": e0.url, "r1": e1.url},
            replica_fails=2, breaker_cooldown_s=0.2, poll_s=30.0,
            chaos=chaos)
        try:
            body = json.dumps({"batch": rows[:2].tolist()}).encode()
            for _ in range(4):
                status, _, _ = router.proxy_predict(body)
                assert status == 200
            assert (router.describe_replicas()["r0"]["breaker"]["state"]
                    == "broken")
            assert router.stats.snapshot()["breaker_opens"] == 1
            time.sleep(0.25)  # past the cooldown: probe time
            deadline = time.monotonic() + 5.0
            while (router.describe_replicas()["r0"]["breaker"]["state"]
                   != "serving" and time.monotonic() < deadline):
                status, _, _ = router.proxy_predict(body)
                assert status == 200
                time.sleep(0.05)
            assert (router.describe_replicas()["r0"]["breaker"]["state"]
                    == "serving")
            assert router.stats.snapshot()["breaker_closes"] >= 1
        finally:
            router.stop()
            e0.stop()
            e1.stop()


# ---------------------------------------------------------------------------
# rolling rollout
# ---------------------------------------------------------------------------


class TestRollout:
    def test_rolling_rollout_shifts_every_replica(self, net, rows,
                                                  tmp_path):
        net2 = small_net(seed=11)
        path = str(tmp_path / "m2.zip")
        ModelSerializer.write_model(net2, path)
        fleet = _fleet(net, 2)
        try:
            code, report = _post_raw(fleet.url, "/rollout",
                                     {"name": "m2", "path": path,
                                      "input_shape": [4]})
            report = json.loads(report)
            assert code == 200 and report["ok"], report
            for eng in fleet.engines().values():
                assert eng.registry.default().key == "m2@v1"
            expect = np.asarray(net2.output(rows[:3]), np.float32)
            code, body = _post_raw(fleet.url, "/predict",
                                   {"batch": rows[:3].tolist()})
            assert code == 200
            np.testing.assert_array_equal(
                np.asarray(json.loads(body)["outputs"], np.float32),
                expect)
            assert fleet.router.stats.snapshot()["rollouts"] == 1
        finally:
            fleet.stop()

    def test_warmup_failure_rolls_back_and_moves_no_default(self, net,
                                                            rows,
                                                            tmp_path):
        """Injected warmup failure on the SECOND replica: the roll stops,
        the first replica is rolled back to its prior default, the
        failing replica's default never moved (registry isolation), and
        traffic through the router still serves the OLD model
        byte-identically."""
        net2 = small_net(seed=11)
        path = str(tmp_path / "m2.zip")
        ModelSerializer.write_model(net2, path)
        fleet = _fleet(net, 2)
        try:
            fleet.engines()["r1"].registry.chaos = ServingChaos(
                ServingChaosConfig(warmup_fail_name="m2"))
            code, report = _post_raw(fleet.url, "/rollout",
                                     {"name": "m2", "path": path,
                                      "input_shape": [4]})
            report = json.loads(report)
            assert code == 409 and not report["ok"]
            assert report["failed_replica"] == "r1"
            assert report["rolled_back"] == ["r0"]
            for eng in fleet.engines().values():
                assert eng.registry.default().key == "default@v1"
            # the half-warmed record is isolated as broken, not serving
            assert (fleet.engines()["r1"].registry.get("m2").state
                    == "broken")
            expect = np.asarray(net.output(rows[:3]), np.float32)
            code, body = _post_raw(fleet.url, "/predict",
                                   {"batch": rows[:3].tolist()})
            assert code == 200
            np.testing.assert_array_equal(
                np.asarray(json.loads(body)["outputs"], np.float32),
                expect)
            assert fleet.router.stats.snapshot()["rollbacks"] == 1
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# fleet-wide SLO shed
# ---------------------------------------------------------------------------


class TestSLOShed:
    def test_low_class_sheds_while_high_class_admits(self, net, rows):
        fleet = _fleet(net, 1, router_kwargs={
            "slo_classes": "interactive:5,batch:60", "queue_cap": 2})
        router = fleet.router
        try:
            # batch (priority 1 of 2) gets ceil(2 * 1/2) = 1 slot;
            # interactive keeps the full cap of 2
            assert router._admit({"slo": "batch"}) == "batch"
            with pytest.raises(FleetOverloadError):
                router._admit({"slo": "batch"})
            assert router._admit({"slo": "interactive"}) == "interactive"
            router._release()
            router._release()
            assert router.stats.snapshot()["shed_by_class"] == {"batch": 1}
            # unlabeled traffic rides the lowest class
            assert router._class_of({}) == ("batch", 1)
            # and the shed is visible on the wire: hold one slot, then a
            # batch-class request 429s with Retry-After while an
            # interactive one still answers
            router._admit({"slo": "batch"})
            try:
                req = urllib.request.Request(
                    fleet.url + "/predict",
                    data=json.dumps({"batch": rows[:1].tolist(),
                                     "slo": "batch"}).encode(),
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=30)
                assert ei.value.code == 429
                assert ei.value.headers["Retry-After"] == "1"
                code, _ = _post_raw(fleet.url, "/predict",
                                    {"batch": rows[:1].tolist(),
                                     "slo": "interactive"})
                assert code == 200
            finally:
                router._release()
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# liveness vs readiness (satellite 1)
# ---------------------------------------------------------------------------


class TestReadinessSplit:
    def test_plain_health_contract_is_byte_unchanged(self, net):
        eng = ServingEngine(model=net).start()
        try:
            code, body = _get(eng.url, "/health")
            assert code == 200
            # the PRE-split body: no live/ready keys on the plain path
            assert set(body) == {"ok", "draining", "model", "models",
                                 "health"}
            code, body = _get(eng.url, "/health?ready=1")
            assert code == 200
            assert body["live"] is True and body["ready"] is True
        finally:
            eng.stop()

    def test_draining_is_alive_but_not_ready(self, net):
        eng = ServingEngine(model=net).start()
        try:
            eng.drain()
            code, body = _get(eng.url, "/health")
            assert code == 503 and body["draining"] is True
            assert "live" not in body  # plain contract untouched
            code, body = _get(eng.url, "/health?ready=1")
            assert code == 503
            assert body["live"] is True and body["ready"] is False
        finally:
            eng.stop()

    def test_drain_stops_admission_without_breaker_vote(self, net, rows):
        fleet = _fleet(net, 2)
        try:
            fleet.engines()["r0"].drain()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                fleet.router.refresh()
                desc = fleet.router.describe_replicas()
                if not desc["r0"]["ready"]:
                    break
                time.sleep(0.05)
            desc = fleet.router.describe_replicas()
            assert desc["r0"]["ready"] is False
            # alive-but-not-ready: NOT death — no breaker vote
            assert desc["r0"]["breaker"]["state"] == "serving"
            code, _ = _post_raw(fleet.url, "/predict",
                                {"batch": rows[:2].tolist()})
            assert code == 200  # routed to r1
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# seal-on-drain (satellite 2): rollout racing shutdown
# ---------------------------------------------------------------------------


class TestSealOnDrain:
    def test_drain_seals_lifecycle_so_no_halfwarmed_default(self, net):
        eng = ServingEngine(model=net).start()
        try:
            # a rollout in progress: v2 loaded but not yet warm
            eng.registry.load("m2", model=small_net(seed=11))
            eng.drain()
            # the racing rollout thread's next steps are REFUSED…
            with pytest.raises(DrainingError):
                eng.registry.warmup("m2")
            with pytest.raises(DrainingError):
                eng.registry.serve("m2")
            # …and over HTTP they answer 503 like any drain-time admission
            code, _ = _post_raw(eng.url, "/models",
                                {"action": "serve", "name": "m2"})
            assert code == 503
            # the serving default never moved off the stable version
            assert eng.registry.default().key == "default@v1"
            # unload stays legal: teardown must still free buffers
            eng.registry.unload("m2")
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


class TestRouterLedger:
    def test_router_stats_rides_the_central_registry(self, net, rows):
        fleet = _fleet(net, 1)
        try:
            _post_raw(fleet.url, "/predict", {"batch": rows[:2].tolist()})
            reg = obs.default_registry()
            assert "router_stats" in reg.ledgers(fleet.router)
            text = reg.render_prometheus()
            # the registry strips the _stats suffix at scrape time
            assert "dl4j_router_requests" in text
            assert "dl4j_router_proxied_ok" in text
            # and the router's own /metrics carries the JSON ledger
            code, body = _get(fleet.url, "/metrics")
            assert code == 200
            assert body["router"]["requests"] >= 1
            assert body["router"]["proxied_ok"] >= 1
        finally:
            fleet.stop()
