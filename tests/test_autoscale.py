"""Autoscaling multi-model fleet tests (ISSUE 20): deterministic
signal-driven scale decisions (same snapshots => same decisions,
bit-exact, twice), HBM-aware first-fit-decreasing placement with
model-affinity routing (a model on zero ready replicas is a LOUD 503,
never a silent wrong-replica answer), per-tenant token-bucket fairness
(one tenant's burst never starves another's admission), the goodbye
ordering fix (addr unlink BEFORE board deregister), the /signals +
/placement + /replicas-HBM surfaces, and the headline chaos contract:
a scripted load wave triggers scale-up, then scale-down races live
/predict and streaming /generate traffic with ZERO failed admitted
requests.

Reference anchor: the reference's scaleout tree provisioned a STATIC
Spark worker set by hand (SURVEY L6 spark/zookeeper) — there is no
component that sizes the fleet or decides where a model runs; every
contract here is beyond-reference.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience import AutoscaleChaos, AutoscaleChaosConfig
from deeplearning4j_tpu.serving.autoscale import (
    FleetAutoscaler,
    ScaleConfig,
)
from deeplearning4j_tpu.serving.fleet import (
    ServingFleet,
    goodbye_replica,
)
from deeplearning4j_tpu.serving.placement import (
    ModelFootprint,
    PlacementPlan,
    model_footprint,
    pack_models,
)
from deeplearning4j_tpu.serving.router import (
    FleetRouter,
    ModelUnplacedError,
    TenantQuotaError,
    publish_replica_addr,
    read_replica_addr,
)
from deeplearning4j_tpu.serving.slo import (
    TenantBucket,
    parse_tenant_quotas,
)

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


def tiny_lm(**over):
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=32, use_flash=False)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


@pytest.fixture(scope="module")
def net():
    return small_net()


def _post_raw(url, path, payload, timeout=60):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _get(url, path, timeout=30):
    try:
        with urllib.request.urlopen(url + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _fleet(net, n=2, **kw):
    kw.setdefault("heartbeat_s", 0.5)
    return ServingFleet(model=net, replicas=n, **kw).start()


def _wait_ready(router, n, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(router.signals()["ready_replicas"]) >= n:
            return
        time.sleep(0.05)
    raise AssertionError(f"fleet never reached {n} ready replicas")


def _stripped(decisions):
    """Decisions minus the enactment fields tick() adds after decide()
    — the pure-decision view replay() reproduces."""
    return [{k: v for k, v in d.items()
             if k not in ("enacted", "enact_error")} for d in decisions]


# ---------------------------------------------------------------------------
# tenant quotas: parsing, the bucket, and admission fairness
# ---------------------------------------------------------------------------


class TestTenantQuotas:
    def test_parse(self):
        qs = parse_tenant_quotas("a:2:5, b:10")
        assert [(q.name, q.rate_per_s, q.burst) for q in qs] == \
            [("a", 2.0, 5.0), ("b", 10.0, 10.0)]
        assert parse_tenant_quotas("") == []
        for bad in ("a", "a:0", "a:-1:2", "a:1:0.5", "a:1,a:2"):
            with pytest.raises(ValueError):
                parse_tenant_quotas(bad)

    def test_bucket_deterministic_clock(self):
        (q,) = parse_tenant_quotas("t:2:2")
        clock = [0.0]
        b = TenantBucket(q, now_fn=lambda: clock[0])
        assert b.try_take() == (True, 0.0)
        assert b.try_take() == (True, 0.0)
        ok, retry = b.try_take()
        assert not ok and retry == pytest.approx(0.5)
        clock[0] = 0.5  # refill one token at 2/s
        assert b.try_take() == (True, 0.0)

    def test_burst_tenant_never_starves_the_other(self, net):
        """The acceptance counter-proof: tenant a's burst exhausts its
        OWN bucket (429 + Retry-After) while tenant b's admission is
        untouched — and a's sheds never consume in-flight headroom."""
        fleet = _fleet(net, 1, router_kwargs={
            "tenant_quotas": "a:0.001:3,b:1000:1000"})
        try:
            router = fleet.router
            a_shed = 0
            for _ in range(10):
                try:
                    router._admit({"tenant": "a"})
                    router._release()
                except TenantQuotaError as e:
                    a_shed += 1
                    assert e.retry_after_s > 0
            assert a_shed == 7  # burst 3 admitted, the rest shed
            for _ in range(20):  # b rides through a's burst untouched
                router._admit({"tenant": "b"})
                router._release()
            snap = router.stats.snapshot()
            assert snap["tenant_admitted"] == {"a": 3, "b": 20}
            assert snap["tenant_shed"] == {"a": 7}
            # tenant sheds are their own ledger, not the SLO shed
            assert snap["fleet_429"] == 0
        finally:
            fleet.stop()

    def test_http_shed_carries_retry_after(self, net):
        fleet = _fleet(net, 1, router_kwargs={"tenant_quotas": "a:0.5:1"})
        try:
            rows = [[0.1, 0.2, 0.3, 0.4]]
            code, _, _ = _post_raw(fleet.url, "/predict",
                                   {"batch": rows, "tenant": "a"})
            assert code == 200
            code, body, headers = _post_raw(
                fleet.url, "/predict", {"batch": rows, "tenant": "a"})
            assert code == 429
            assert int(headers.get("Retry-After")) >= 1
            assert "tenant" in json.loads(body)["error"]
            # unmetered traffic still flows
            code, _, _ = _post_raw(fleet.url, "/predict", {"batch": rows})
            assert code == 200
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# placement: FFD determinism, unplaced loudness, affinity routing
# ---------------------------------------------------------------------------


class TestPlacement:
    def test_ffd_deterministic_and_unplaced(self):
        GB = 2 ** 30
        fps = [ModelFootprint("big", 6 * GB),
               ModelFootprint("mid", 3 * GB, kv_bytes=1 * GB),
               ModelFootprint("small", 1 * GB),
               ModelFootprint("huge", 40 * GB)]
        plans = [pack_models(fps, ["r1", "r0"], hbm_gb=8.0)
                 for _ in range(2)]
        assert plans[0].describe() == plans[1].describe()
        plan = plans[0]
        # FFD: big (6G) -> r0; mid (4G) won't fit r0 -> r1; small-> r0
        assert plan.assignments == {"r0": ["big", "small"],
                                    "r1": ["mid"]}
        assert plan.unplaced == ["huge"]
        assert plan.replicas_of("small") == ["r0"]
        assert plan.replicas_of("huge") == []
        desc = plan.describe()
        assert desc["utilization"]["r0"] == pytest.approx(0.875)
        assert "huge" in desc["footprints"]

    def test_model_footprint_prices_params_and_kv(self):
        lm = tiny_lm()
        fp = model_footprint("lm", lm, ann_bytes=123, hbm_gb=0.25)
        assert fp.param_bytes > 0
        assert fp.kv_bytes > 0  # decode-eligible => a KV arena is priced
        assert fp.ann_bytes == 123
        assert fp.total_bytes == fp.param_bytes + fp.kv_bytes + 123
        net = small_net()
        fp2 = model_footprint("mlp", net)
        assert fp2.kv_bytes == 0  # no generate surface, no arena

    def test_affinity_routes_only_to_holders(self, net):
        fleet = _fleet(net, 2)
        try:
            _wait_ready(fleet.router, 2)
            plan = PlacementPlan(budget_bytes=2 ** 30,
                                 assignments={"r0": ["default"], "r1": []},
                                 used_bytes={"r0": 100, "r1": 0})
            fleet.router.set_placement(plan)
            rows = [[0.1, 0.2, 0.3, 0.4]]
            for _ in range(6):
                code, _, _ = _post_raw(fleet.url, "/predict",
                                       {"batch": rows, "model": "default"})
                assert code == 200
            engines = fleet.engines()
            assert engines["r0"].stats.snapshot()["requests"] == 6
            assert engines["r1"].stats.snapshot()["requests"] == 0
        finally:
            fleet.stop()

    def test_zero_ready_holders_is_a_loud_503(self, net):
        """A model placed nowhere (or on dead holders) answers 503
        naming the model — never a silent wrong-replica 500."""
        fleet = _fleet(net, 1)
        try:
            _wait_ready(fleet.router, 1)
            plan = PlacementPlan(budget_bytes=2 ** 30,
                                 assignments={"r0": []},
                                 used_bytes={"r0": 0},
                                 unplaced=["default"])
            fleet.router.set_placement(plan)
            with pytest.raises(ModelUnplacedError, match="default"):
                fleet.router._candidates(model="default")
            rows = [[0.1, 0.2, 0.3, 0.4]]
            code, body, _ = _post_raw(fleet.url, "/predict",
                                      {"batch": rows, "model": "default"})
            assert code == 503
            assert "default" in json.loads(body)["error"]
            assert fleet.router.stats.snapshot()["affinity_503"] >= 2
            # an UNKNOWN model keeps the fleet-wide walk (the plan only
            # constrains models it priced)
            code, _, _ = _post_raw(fleet.url, "/predict", {"batch": rows})
            assert code == 200
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# surfaces: /signals, /placement, /replicas HBM
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_signals_and_placement_and_hbm(self, net):
        fleet = _fleet(net, 2)
        try:
            _wait_ready(fleet.router, 2)
            code, sig = _get(fleet.url, "/signals")
            assert code == 200
            assert sorted(sig["replicas"]) == ["r0", "r1"]
            for entry in sig["replicas"].values():
                assert set(entry) >= {"ready", "role", "breaker",
                                      "queue_depth", "cordoned"}
            assert sig["ready_replicas"] == ["r0", "r1"]
            for key in ("queue_depth", "inflight", "shed_total",
                        "shed_by_class", "per_class_latency_ms",
                        "slo_classes", "tenant_admitted", "tenant_shed",
                        "affinity_503"):
                assert key in sig
            code, rep = _get(fleet.url, "/placement")
            assert code == 200 and rep == {"placement": None}
            auto = FleetAutoscaler(fleet, config=ScaleConfig())
            plan = auto.plan_placement(
                [model_footprint("default", net)])
            code, rep = _get(fleet.url, "/placement")
            assert code == 200
            assert rep["placement"] == plan.describe()
            # /replicas now carries the AOT-priced HBM block
            code, reps = _get(fleet.url, "/replicas")
            assert code == 200
            for rid in ("r0", "r1"):
                hbm = reps[rid]["hbm"]
                assert hbm["budget_bytes"] > 0
                assert hbm["used_bytes"] > 0
                assert hbm["models"]["default"]["param_bytes"] > 0
                assert hbm["utilization"] == pytest.approx(
                    hbm["used_bytes"] / hbm["budget_bytes"], rel=1e-3)
        finally:
            fleet.stop()

    def test_engine_metrics_hbm_report(self, net):
        from deeplearning4j_tpu.serving import ServingEngine

        eng = ServingEngine(model=net).start()
        try:
            code, m = _get(eng.url, "/metrics")
            assert code == 200
            assert m["hbm"]["used_bytes"] > 0
            assert m["hbm"]["models"]["default"]["kv_bytes"] == 0
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# the goodbye ordering fix (satellite: stale addr can't outlive the board)
# ---------------------------------------------------------------------------


class TestGoodbyeOrdering:
    def test_addr_unlinked_before_deregister(self, tmp_path):
        root = str(tmp_path)
        publish_replica_addr(root, "rX", "http://127.0.0.1:1")
        order = []

        class Board:
            def deregister_worker(self, rid):
                # the addr must ALREADY be gone when the board goodbye
                # lands — the crash window between the two steps now
                # leaves a board entry (expiry reaps it), never a
                # stale addr file (nothing reaps those)
                order.append(("dereg", rid,
                              read_replica_addr(root, "rX")))

        goodbye_replica(Board(), root, "rX")
        assert order == [("dereg", "rX", None)]

    def test_board_failure_still_removed_addr(self, tmp_path):
        root = str(tmp_path)
        publish_replica_addr(root, "rX", "http://127.0.0.1:1")

        class Board:
            def deregister_worker(self, rid):
                raise OSError("board transport died")

        with pytest.raises(OSError):
            goodbye_replica(Board(), root, "rX")
        assert read_replica_addr(root, "rX") is None


# ---------------------------------------------------------------------------
# decision determinism: same snapshots => same decisions, bit-exact
# ---------------------------------------------------------------------------


def _snap(ready, queue, shed=0, p99_ms=None, deadline_s=5.0):
    lat = {}
    if p99_ms is not None:
        lat["default"] = {"p50": p99_ms / 2, "p99": p99_ms, "count": 10}
    return {"ready_replicas": [f"r{i}" for i in range(ready)],
            "queue_depth": queue, "shed_total": shed,
            "slo_classes": [{"name": "default", "deadline_s": deadline_s}],
            "per_class_latency_ms": lat}


class TestDeterministicDecisions:
    CFG = ScaleConfig(min_replicas=1, max_replicas=3, up_queue=8.0,
                      up_p99_frac=0.8, up_shed=1, window=2,
                      down_queue=0.0, cooldown=1)

    def scripted(self):
        return ([_snap(1, 20)] * 2            # queue wave -> up
                + [_snap(2, 0)] * 4           # idle -> (cooldown) down
                + [_snap(1, 0, shed=0)]       # at min: hold
                + [_snap(1, 1, p99_ms=4500)] * 3   # p99 pressure -> up
                + [_snap(1, 0, shed=5), _snap(1, 0, shed=10)])  # sheds

    def test_replay_bit_exact_and_votes(self):
        decs = FleetAutoscaler.replay(self.scripted(), config=self.CFG)
        assert decs == FleetAutoscaler.replay(self.scripted(),
                                              config=self.CFG)
        actions = [d["action"] for d in decs]
        assert actions.count("up") >= 2 and actions.count("down") >= 1
        assert decs[1]["action"] == "up" and decs[1]["votes"] == ["queue"]
        down = next(d for d in decs if d["action"] == "down")
        assert down["victim"] == "r1"  # highest rid among ready
        assert any("p99" in d["votes"] for d in decs)
        assert any("shed" in d["votes"] for d in decs)

    def test_bounds_and_cooldown(self):
        cfg = ScaleConfig(min_replicas=1, max_replicas=1, window=1,
                          cooldown=2)
        decs = FleetAutoscaler.replay(
            [_snap(1, 50)] * 2 + [_snap(1, 0)] * 3, config=cfg)
        assert [d["action"] for d in decs] == ["hold"] * 5
        assert decs[0]["reason"] == "at_max"
        assert decs[1]["reason"] == "cooldown"
        assert any(d["reason"] == "at_min" for d in decs[2:])

    def test_chaos_overlay_is_deterministic_input_corruption(self):
        cc = AutoscaleChaos(AutoscaleChaosConfig(
            load_wave={"at_tick": 1, "ticks": 2, "queue_depth": 40,
                       "sheds_per_tick": 3}))
        base = {"ready_replicas": ["r0"], "queue_depth": 0,
                "shed_total": 0}
        outs = [cc.on_signals(t, dict(base)) for t in range(4)]
        assert outs[0]["queue_depth"] == 0
        assert [o["queue_depth"] for o in outs[1:3]] == [40, 40]
        assert [o["shed_total"] for o in outs[1:3]] == [3, 6]
        assert outs[3]["queue_depth"] == 0
        assert len(cc.log) == 2


# ---------------------------------------------------------------------------
# the headline chaos contract: wave -> scale-up -> scale-down under
# live traffic, zero failed admitted requests, decisions replayable
# ---------------------------------------------------------------------------


class TestScaleChaos:
    def test_wave_up_then_down_under_predict_traffic(self, net):
        cfg = ScaleConfig(min_replicas=1, max_replicas=2, up_queue=10.0,
                          up_shed=0, window=2, down_queue=0.5, cooldown=1)
        fleet = _fleet(net, 1)
        auto = FleetAutoscaler(
            fleet, config=cfg,
            chaos=AutoscaleChaos(AutoscaleChaosConfig(
                load_wave={"at_tick": 0, "ticks": 2, "queue_depth": 50})))
        failures, codes = [], []
        stop = threading.Event()
        rows = [[0.1, 0.2, 0.3, 0.4]]

        def hammer():
            while not stop.is_set():
                try:
                    code, _, _ = _post_raw(fleet.url, "/predict",
                                           {"batch": rows})
                    codes.append(code)
                    if code != 200:
                        failures.append(code)
                except OSError as e:  # connect failure = a lost request
                    failures.append(f"{e}")
                time.sleep(0.005)

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        try:
            _wait_ready(fleet.router, 1)
            for t in threads:
                t.start()
            d0, d1 = auto.tick(), auto.tick()
            assert [d0["action"], d1["action"]] == ["hold", "up"]
            assert d1["enacted"] == "r1"
            _wait_ready(fleet.router, 2)
            down = None
            for _ in range(8):  # quiet ticks walk cooldown+window to down
                d = auto.tick()
                if d["action"] == "down":
                    down = d
                    break
            assert down is not None and down["victim"] == "r1"
            assert down["enacted"] == "r1"
            # the victim drained through the goodbye path: board + addr
            # agree it is gone, and traffic kept flowing the whole time
            assert read_replica_addr(fleet.fleet_dir, "r1") is None
            time.sleep(0.3)  # a last full round of hammer traffic
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            fleet.stop()
        assert not failures, f"failed admitted requests: {failures[:5]}"
        assert len(codes) > 20  # the hammer actually exercised the window
        # the recorded run replays bit-exact from its own signals_log
        assert _stripped(auto.decisions) == FleetAutoscaler.replay(
            auto.signals_log, config=cfg)
        snap = auto.stats.snapshot()
        assert snap["scale_ups"] == 1 and snap["scale_downs"] == 1
        assert snap["enact_failures"] == 0

    def test_scale_down_races_live_generate_stream(self):
        """Scale-down drains the victim through the goodbye path while
        a /generate stream is mid-flight ON the victim: the stream
        finishes (done record, full token count), nothing 5xxs."""
        # down_queue is generous: live streams keep a small real queue
        # depth, and the contract under test is the drain, not the vote
        cfg = ScaleConfig(min_replicas=1, max_replicas=2, up_queue=20.0,
                          up_shed=0, window=1, down_queue=10.0, cooldown=0)
        lm = tiny_lm()
        fleet = ServingFleet(
            model=lm, replicas=1, heartbeat_s=0.5,
            engine_kwargs={"kv_block": 8, "kv_blocks": 16}).start()
        auto = FleetAutoscaler(
            fleet, config=cfg,
            chaos=AutoscaleChaos(AutoscaleChaosConfig(
                load_wave={"at_tick": 0, "ticks": 1, "queue_depth": 50})))
        results, failures = [], []

        def stream_one():
            try:
                req = urllib.request.Request(
                    fleet.url + "/generate",
                    data=json.dumps({"tokens": [1, 5, 2, 9], "n_new": 12,
                                     "temperature": 0.0,
                                     "stream": True}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as resp:
                    events = [json.loads(ln)
                              for ln in resp.read().splitlines()
                              if ln.strip()]
                done = [e for e in events if e.get("done")]
                if done and len(done[0]["tokens"]) == 12:
                    results.append(done[0]["tokens"])
                else:
                    failures.append(f"incomplete stream: {events[-2:]}")
            except (OSError, urllib.error.HTTPError) as e:
                failures.append(f"{e}")

        try:
            _wait_ready(fleet.router, 1)
            d0 = auto.tick()
            assert d0["action"] == "up" and d0["enacted"] == "r1"
            _wait_ready(fleet.router, 2)
            # streams land on BOTH replicas (round-robin walk), so at
            # least one is mid-flight on the victim when the drain hits
            threads = [threading.Thread(target=stream_one)
                       for _ in range(4)]
            for t in threads:
                t.start()
            time.sleep(0.15)  # let the streams admit + start ticking
            down = auto.tick()
            assert down["action"] == "down" and down["enacted"] == "r1"
            for t in threads:
                t.join(timeout=120)
            assert not failures, f"failed streams: {failures}"
            assert len(results) == 4
            assert all(r == results[0] for r in results)  # greedy, equal
            # new traffic keeps flowing on the survivor
            code, body, _ = _post_raw(
                fleet.url, "/generate",
                {"tokens": [1, 5, 2, 9], "n_new": 4, "temperature": 0.0})
            assert code == 200
        finally:
            fleet.stop()
        assert _stripped(auto.decisions) == FleetAutoscaler.replay(
            auto.signals_log, config=cfg)


# ---------------------------------------------------------------------------
# knob / ledger / bench-leg registration
# ---------------------------------------------------------------------------


class TestRegistration:
    def test_knobs_registered(self):
        from deeplearning4j_tpu.ops import env as envknob

        for name in ("DL4J_TPU_SERVE_SCALE_MIN",
                     "DL4J_TPU_SERVE_SCALE_MAX",
                     "DL4J_TPU_SERVE_SCALE_UP_QUEUE",
                     "DL4J_TPU_SERVE_SCALE_UP_P99_FRAC",
                     "DL4J_TPU_SERVE_SCALE_UP_SHED",
                     "DL4J_TPU_SERVE_SCALE_WINDOW",
                     "DL4J_TPU_SERVE_SCALE_DOWN_QUEUE",
                     "DL4J_TPU_SERVE_SCALE_COOLDOWN",
                     "DL4J_TPU_SERVE_TENANT_QUOTAS"):
            assert envknob.knob(name) is not None

    def test_autoscale_ledger_registered(self):
        from deeplearning4j_tpu import obs

        auto = FleetAutoscaler(config=ScaleConfig())
        ledgers = obs.default_registry().ledgers(auto)
        assert "autoscale_stats" in ledgers
        snap = ledgers["autoscale_stats"].snapshot()
        assert snap["ticks"] == 0 and "scale_ups" in snap
