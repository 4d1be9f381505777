"""Unified observability plane (ISSUE 7 — deeplearning4j_tpu/obs/).

Contracts under test:

  * obs DISABLED (the default) => training is BIT-exact vs obs enabled —
    spans are host-side events that never enter the numerics (the
    acceptance bar's equivalence clause);
  * span tracer: monotonic spans with ids + parent ids + attrs, nested
    parenting, null-path no-ops, after-the-fact waits;
  * MetricsRegistry: counters/gauges/histograms, ledger adoption (every
    ``net.*_stats`` ledger on MLN/CG registers — a new ledger added
    without registration fails LOUDLY here), Prometheus text exposition
    pinned by a golden file (label escaping, histogram buckets) plus
    counter monotonicity across two scrapes;
  * one scrape covers all five ledgers (dispatch/memory/pipeline/
    resilience/serving) through the serving engine's /metrics;
  * flight recorder: bounded ring, crash-safe flush, fsync-on-preemption
    through the ResilientTrainer SIGTERM path, checkpoint/membership
    correlation events;
  * instrumented seams emit the expected spans (dispatch trace-vs-cache-
    hit, serve.request -> serve.batch -> dispatch parenting with the
    request id threading through the batcher, etl waits, ckpt phases).

Reference provenance: the listener/UI plane these tests grow from is
deeplearning4j-core/.../optimize/api/IterationListener.java and
deeplearning4j-ui-parent (UiServer.java) — see PARITY.md.
"""

import json
import os
import urllib.request

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.obs.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "prometheus_golden.txt")


@pytest.fixture
def obs_on():
    """Force the gate on with a FRESH tracer/journal (the module
    singletons are process-wide; tests must not read each other's
    spans)."""
    obs.set_enabled(True)
    obs.tracer().clear()
    try:
        yield
    finally:
        obs.set_enabled(None)


def mlp(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .updater("adam").list()
            .layer(0, DenseLayer(n_in=6, n_out=12, activation="relu"))
            .layer(1, OutputLayer(n_in=12, n_out=3, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def data(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_spans_nest_with_parent_ids(obs_on):
    with obs.span("outer", a=1) as sp_outer:
        with obs.span("inner") as sp_inner:
            sp_inner.set_attr("x", "y")
        assert sp_inner.parent_id == sp_outer.span_id
    spans = obs.tracer().spans()
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["parent_id"] is None
    assert by_name["inner"]["attrs"] == {"x": "y"}
    assert by_name["outer"]["attrs"] == {"a": 1}
    assert by_name["outer"]["duration_s"] >= by_name["inner"]["duration_s"]


def test_disabled_tracer_records_nothing():
    obs.set_enabled(False)
    try:
        obs.tracer().clear()
        with obs.span("nope", k=1) as sp:
            sp.set_attr("still", "a no-op")  # null span: same call shape
        obs.record_span("nope2", 0.5)
        assert obs.tracer().spans() == []
    finally:
        obs.set_enabled(None)


def test_env_gate_default_off(monkeypatch):
    monkeypatch.delenv(obs.ENV_OBS, raising=False)
    assert not obs.obs_enabled()
    monkeypatch.setenv(obs.ENV_OBS, "1")
    assert obs.obs_enabled()
    monkeypatch.setenv(obs.ENV_OBS, "0")
    assert not obs.obs_enabled()


def test_record_span_backdates_start(obs_on):
    obs.record_span("wait", 0.25, seq=3)
    (s,) = obs.tracer().spans("wait")
    assert abs(s["duration_s"] - 0.25) < 1e-6
    assert s["attrs"]["seq"] == 3


def test_span_ring_is_bounded():
    tr = obs.Tracer(capacity=8)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    spans = tr.spans()
    assert len(spans) == 8
    assert spans[-1]["name"] == "s49"


# ---------------------------------------------------------------------------
# the acceptance equivalence: obs on vs off is BIT-exact
# ---------------------------------------------------------------------------


def test_training_bit_exact_with_obs_on_vs_off():
    """Spans/journal/registry are host-side observers: the same seed with
    DL4J_TPU_OBS flipped must produce bit-identical params and losses —
    the contract that makes default-off obs equal to pre-PR behavior."""
    x, y = data(48)

    def run():
        net = mlp()
        losses = [net.fit(x, y) for _ in range(5)]
        return losses, net.params

    obs.set_enabled(False)
    try:
        losses_off, params_off = run()
    finally:
        obs.set_enabled(None)
    obs.set_enabled(True)
    try:
        losses_on, params_on = run()
    finally:
        obs.set_enabled(None)
    assert losses_off == losses_on
    for a, b in zip(jax.tree_util.tree_leaves(params_off),
                    jax.tree_util.tree_leaves(params_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    r.counter("dl4j_c", 2, k="a")
    r.counter("dl4j_c", 3, k="a")
    r.gauge("dl4j_g", 1.5)
    r.gauge("dl4j_g", 2.5)  # last write wins
    for v in (0.001, 0.2):
        r.histogram("dl4j_h", v, buckets=(0.01, 0.1))
    snap = r.snapshot()
    assert snap["counters"]["dl4j_c"]["k=a"] == 5
    assert snap["gauges"]["dl4j_g"]["_"] == 2.5
    h = snap["histograms"]["dl4j_h"]["_"]
    assert h["count"] == 2 and h["counts"] == [1, 0, 1]
    with pytest.raises(ValueError):
        r.counter("dl4j_c", -1)  # counters are monotonic by construction


def test_prometheus_exposition_matches_golden_file():
    """The exact text exposition is pinned: label escaping (backslash,
    quote, newline), sorted labels, histogram buckets with +Inf/_sum/
    _count, counter _total naming, HELP/TYPE metadata."""
    r = MetricsRegistry()
    r.set_help("dl4j_requests", "serving requests accepted")
    r.counter("dl4j_requests", 3, model="mnist@v1", path="/predict")
    r.counter("dl4j_requests", 1, model='with"quote\\and\nnewline',
              path="/predict")
    r.gauge("dl4j_queue_depth", 7)
    for v in (0.003, 0.02, 0.33, 0.5055):
        r.histogram("dl4j_latency_seconds", v, buckets=(0.005, 0.05, 0.5),
                    model="mnist@v1")
    with open(GOLDEN) as f:
        assert r.render_prometheus() == f.read()


def test_counter_monotonicity_across_two_scrapes():
    r = MetricsRegistry()
    r.counter("dl4j_events", 2)
    first = {line.split(" ")[0]: float(line.split(" ")[1])
             for line in r.render_prometheus().splitlines()
             if not line.startswith("#")}
    r.counter("dl4j_events", 1)
    second = {line.split(" ")[0]: float(line.split(" ")[1])
              for line in r.render_prometheus().splitlines()
              if not line.startswith("#")}
    for name, v in first.items():
        assert second[name] >= v, f"{name} went backwards"
    assert second["dl4j_events_total"] == 3


def _assert_all_ledgers_registered(net, registry) -> None:
    """THE registration convention: every non-None ``*_stats`` attribute
    on a container must be a registered registry view."""
    registered = registry.ledgers(net)
    for attr, val in vars(net).items():
        if attr.endswith("_stats") and val is not None:
            assert registered.get(attr) is val, (
                f"net.{attr} is not registered in the MetricsRegistry — "
                "new ledgers must go through obs.registry.register_net "
                "at their attach point")


def test_every_mln_ledger_registers():
    net = mlp()
    _assert_all_ledgers_registered(net, obs.default_registry())


def test_every_cg_ledger_registers():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = (NeuralNetConfiguration.builder().seed(3).learning_rate(0.1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("out", OutputLayer(
                n_in=6, n_out=3, activation="softmax",
                loss_function="mcxent"), "in")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    _assert_all_ledgers_registered(net, obs.default_registry())


def test_unregistered_new_ledger_fails_loudly():
    """The guard has teeth: a hypothetical new ledger attached WITHOUT
    registration trips the convention check."""
    net = mlp()
    net.shiny_new_stats = {"things": 1}
    with pytest.raises(AssertionError, match="shiny_new_stats"):
        _assert_all_ledgers_registered(net, obs.default_registry())


def test_dead_owner_is_pruned():
    r = MetricsRegistry()

    class Owner:
        pass

    o = Owner()
    r.register_ledger(o, "x_stats", {"n": 1})
    assert r.collect_ledger_samples()
    del o
    assert r.collect_ledger_samples() == []


# ---------------------------------------------------------------------------
# one scrape, five ledgers (the acceptance bar's export clause)
# ---------------------------------------------------------------------------


def test_one_scrape_covers_all_five_ledgers(obs_on, tmp_path):
    """dispatch + memory + pipeline + resilience + serving counters in a
    single /metrics scrape of the serving engine (Prometheus form)."""
    from deeplearning4j_tpu.etl.pipeline import InputPipeline
    from deeplearning4j_tpu.resilience import ResilientTrainer
    from deeplearning4j_tpu.serving.engine import ServingEngine

    x, y = data(32)
    net = mlp()
    net.measure_memory(x[:16], y[:16])  # populates the memory ledger
    pipe = InputPipeline(ListDataSetIterator(x, y, batch=16), workers=1,
                         shard=None)
    trainer = ResilientTrainer(net, handle_signals=False)
    trainer.fit(pipe, num_epochs=1)
    net.pipeline_stats = pipe.pipeline_stats
    obs.register_net(net)
    eng = ServingEngine(model=net).start()
    try:
        eng.predict(x[:4])
        req = urllib.request.Request(
            eng.url + "/metrics", headers={"Accept": "text/plain"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert "text/plain" in r.headers.get("Content-Type", "")
            page = r.read().decode()
    finally:
        eng.stop()
    for family in ("dl4j_dispatch_", "dl4j_memory_", "dl4j_pipeline_",
                   "dl4j_resilience_", "dl4j_serving_"):
        assert any(line.startswith(family)
                   for line in page.splitlines()), f"{family} missing"


def test_metrics_json_contract_unchanged(obs_on):
    from deeplearning4j_tpu.serving.engine import ServingEngine

    x, y = data(8)
    eng = ServingEngine(model=mlp()).start()
    try:
        eng.predict(x[:2])
        with urllib.request.urlopen(eng.url + "/metrics", timeout=10) as r:
            m = json.loads(r.read())
        assert "serving" in m and "models" in m
        req = urllib.request.Request(
            eng.url + "/metrics?format=prometheus")
        with urllib.request.urlopen(req, timeout=10) as r:
            page = r.read().decode()
        assert any(line.startswith("dl4j_serving_")
                   for line in page.splitlines())
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_journal_ring_bounded_and_loadable(tmp_path):
    j = obs.FlightRecorder(path=str(tmp_path / "j.jsonl"), capacity=5,
                           flush_interval_s=1e9)
    for i in range(12):
        j.record("tick", i=i)
    path = j.flush(fsync=True)
    events = obs.FlightRecorder.load(path)
    assert [e["i"] for e in events] == list(range(7, 12))
    assert all(e["kind"] == "tick" for e in events)
    # seq is globally increasing even though the ring dropped the head
    assert [e["seq"] for e in events] == list(range(8, 13))


def test_marker_events_survive_span_floods(tmp_path):
    """Per-dispatch spans enter the journal at hundreds/sec and turn the
    main ring over fast; checkpoint/membership/preempt markers must
    survive the flood (the pinned side ring) or the post-mortem loses
    its anchors."""
    j = obs.FlightRecorder(path=str(tmp_path / "j.jsonl"), capacity=64,
                           flush_interval_s=1e9)
    j.record("checkpoint", step=7)
    j.record("membership", epoch=2)
    for i in range(500):  # > 7x ring turnover of span traffic
        j.append({"kind": "span", "name": f"dispatch.x{i}"})
    events = obs.FlightRecorder.load(j.flush(fsync=True))
    kinds = [e["kind"] for e in events]
    assert "checkpoint" in kinds and "membership" in kinds
    assert [e for e in events if e["kind"] == "checkpoint"][0]["step"] == 7
    # markers also stay visible on the live read surface
    assert j.events("membership")[0]["epoch"] == 2
    # the timeline stays seq-ordered despite the two-ring merge
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)


def test_journal_flush_is_atomic_no_tmp_litter(tmp_path):
    j = obs.FlightRecorder(path=str(tmp_path / "j.jsonl"), capacity=4)
    j.record("a")
    j.flush()
    j.record("b")
    j.flush(fsync=True)
    assert sorted(p.name for p in tmp_path.iterdir()) == ["j.jsonl"]


def test_preemption_fsyncs_journal(obs_on, tmp_path, monkeypatch):
    """The SIGTERM path: checkpoint-before-death also flushes the flight
    recorder with fsync, and the on-disk timeline carries the preempt
    marker + the checkpoint correlation id."""
    import deeplearning4j_tpu.obs.journal as journal_mod
    from deeplearning4j_tpu.resilience import (
        CheckpointManager,
        Preempted,
        ResilientTrainer,
    )

    jr = obs.FlightRecorder(path=str(tmp_path / "flight.jsonl"),
                            capacity=64, flush_interval_s=1e9)
    monkeypatch.setattr(journal_mod, "_DEFAULT", jr)
    x, y = data(32)
    it = ListDataSetIterator(x, y, batch=16)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), every_steps=1,
                            async_save=False)
    trainer = ResilientTrainer(mlp(), mgr, handle_signals=False)

    class _PreemptAfterFirstStep:  # the signal handler's flag, scripted
        def before_step(self, step):
            pass

        def after_step(self, step):
            trainer._preempt_requested = True

    trainer.chaos = _PreemptAfterFirstStep()
    with pytest.raises(Preempted):
        trainer.fit(it, num_epochs=1)
    events = obs.FlightRecorder.load(str(tmp_path / "flight.jsonl"))
    kinds = [e["kind"] for e in events]
    assert "preempt" in kinds and "checkpoint" in kinds
    preempt = [e for e in events if e["kind"] == "preempt"][-1]
    assert preempt["path"] and preempt["step"] == 1
    assert trainer.resilience_stats["last_checkpoint_step"] == 1


def test_checkpoint_spans_and_journal_event(obs_on, tmp_path):
    from deeplearning4j_tpu.resilience import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(mlp(), step=3)
    names = {s["name"] for s in obs.tracer().spans()}
    assert {"ckpt.snapshot", "ckpt.write", "ckpt.commit"} <= names
    write = obs.tracer().spans("ckpt.write")[-1]
    assert write["attrs"]["step"] == 3


# ---------------------------------------------------------------------------
# instrumented seams
# ---------------------------------------------------------------------------


def test_dispatch_spans_mark_trace_vs_cache_hit(obs_on):
    net = mlp()
    x, y = data(16)
    net.fit(x, y)
    net.fit(x, y)
    spans = obs.tracer().spans("dispatch.train_step")
    assert len(spans) == 2
    assert spans[0]["attrs"].get("traced") is True
    assert "traced" not in spans[1]["attrs"]  # compiled-cache hit
    assert spans[0]["duration_s"] > spans[1]["duration_s"]


def test_request_id_threads_through_batcher_to_jit(obs_on):
    """request -> batch -> jit: the serve.request span carries the rid,
    the serve.batch span lists it in request_ids, and the jit dispatch
    span is a CHILD of the batch span (worker-thread parenting)."""
    from deeplearning4j_tpu.serving.engine import ServingEngine

    x, y = data(8)
    eng = ServingEngine(model=mlp()).start()
    try:
        eng.predict(x[:2])
    finally:
        eng.stop()
    requests = obs.tracer().spans("serve.request")
    batches = obs.tracer().spans("serve.batch")
    assert requests and batches
    rid = requests[-1]["attrs"]["rid"]
    batch = batches[-1]
    assert rid in batch["attrs"]["request_ids"]
    children = [s for s in obs.tracer().spans("dispatch.output")
                if s["parent_id"] == batch["span_id"]]
    assert children, "jit dispatch span did not parent under serve.batch"


def test_etl_spans(obs_on):
    from deeplearning4j_tpu.etl.pipeline import InputPipeline

    x, y = data(48)
    pipe = InputPipeline(ListDataSetIterator(x, y, batch=16), workers=1,
                         shard=None)
    assert sum(1 for _ in pipe) == 3
    waits = obs.tracer().spans("etl.wait")
    stages = obs.tracer().spans("etl.stage")
    assert len(waits) == 3 and len(stages) == 3
    assert all(w["attrs"]["records"] == 16 for w in waits)


def test_fleet_round_span_carries_membership_epoch(obs_on):
    from deeplearning4j_tpu.parallel.fleet import (
        ElasticParameterAveragingTrainer,
    )

    x, y = data(32, seed=2)
    net = mlp(seed=11)
    fleet = ElasticParameterAveragingTrainer(net, num_workers=2,
                                             heartbeat_s=2.0)
    try:
        fleet.fit(x, y)
    finally:
        fleet.close()
    rounds = obs.tracer().spans("fleet.round")
    assert rounds and rounds[-1]["attrs"]["membership_epoch"] >= 1
    assert rounds[-1]["attrs"]["workers"] == 2
    splits = obs.tracer().spans("fleet.split")
    assert {s["attrs"]["split"] for s in splits} == {0, 1}
    # the membership journal event correlates with the same epoch
    members = [e for e in obs.default_journal().events("membership")]
    assert members and members[-1]["epoch"] == \
        rounds[-1]["attrs"]["membership_epoch"]


# ---------------------------------------------------------------------------
# exporter + listener + bench registration
# ---------------------------------------------------------------------------


def test_exporter_endpoints(obs_on, tmp_path):
    reg = MetricsRegistry()
    reg.counter("dl4j_things", 4)
    jr = obs.FlightRecorder(path=str(tmp_path / "j.jsonl"))
    jr.record("hello", x=1)
    exp = obs.MetricsExporter(registry=reg, journal=jr).start()
    try:
        with urllib.request.urlopen(exp.url + "/metrics", timeout=10) as r:
            assert b"dl4j_things_total 4" in r.read()
        with urllib.request.urlopen(exp.url + "/metrics.json",
                                    timeout=10) as r:
            snap = json.loads(r.read())
            assert snap["counters"]["dl4j_things"]["_"] == 4
        with urllib.request.urlopen(exp.url + "/journal", timeout=10) as r:
            lines = r.read().decode().strip().splitlines()
            assert json.loads(lines[-1])["kind"] == "hello"
        with urllib.request.urlopen(exp.url + "/health", timeout=10) as r:
            assert json.loads(r.read())["ok"] is True
    finally:
        exp.stop()


def test_stats_listeners_share_uniform_renderer():
    """Satellite: Dispatch/Resilience listeners are ONE StatsListener
    base — same snapshot shape as before, same log format for any
    ledger."""
    from deeplearning4j_tpu.optimize.listeners import (
        DispatchStatsListener,
        PipelineStatsListener,
        ResilienceStatsListener,
        StatsListener,
    )

    assert issubclass(DispatchStatsListener, StatsListener)
    assert issubclass(ResilienceStatsListener, StatsListener)
    assert issubclass(PipelineStatsListener, StatsListener)
    net = mlp()
    x, y = data(16)
    net.resilience_stats = {"retries": 2, "backoff_seconds": 0.5}
    dl = DispatchStatsListener(frequency=1)
    rl = ResilienceStatsListener(frequency=1)
    net.set_listeners(dl, rl)
    net.fit(x, y)
    # stored snapshot shape is backward-compatible (iteration rides along)
    assert dl.snapshots[-1]["traces"]["train_step"] == 1
    assert rl.snapshots[-1]["retries"] == 2
    # ONE render format: sorted key=value, dicts collapsed to sums
    out = dl.render(dl.snapshots[-1])
    assert "traces=1" in out and "donated_steps=" in out
    out = rl.render(rl.snapshots[-1])
    assert "retries=2" in out and "backoff_seconds=0.500" in out
