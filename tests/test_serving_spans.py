"""One span tree from a streamed ``/generate`` down to the decode tick
(ISSUE 26): the request span opens in the engine and closes where the
stream ends, on another thread if need be; the paged decoder hangs the
queue wait, the admission (booking and dispatch) and the tick's phases
under it, every span of a request carrying its ``rid``; a live span is
also a ``jax.profiler.TraceAnnotation``, so a profiler session holds the
program's names on its own clock; the ring counts what it drops; and with
tracing off nothing is recorded and the tokens are the same.

Tiny model, CPU. No timing is asserted beyond order and containment.
"""

from __future__ import annotations

import glob
import os
import threading

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.obs.registry import MetricsRegistry
from deeplearning4j_tpu.serving import ServingEngine

EPS = 1e-5   # spans round to the microsecond


def tiny_lm(**over):
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=32, use_flash=False)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


@pytest.fixture
def obs_on():
    obs.set_enabled(True)
    obs.tracer().clear()
    yield obs.tracer()
    obs.set_enabled(None)
    obs.tracer().clear()


@pytest.fixture(scope="module")
def lm():
    return tiny_lm()


def _end(s):
    return s["t_mono"] + s["duration_s"]


def _inside(child, parent):
    return (child["t_mono"] >= parent["t_mono"] - EPS
            and _end(child) <= _end(parent) + EPS)


def _one(tr, name, **attrs):
    got = [s for s in tr.spans(name)
           if all(s["attrs"].get(k) == v for k, v in attrs.items())]
    assert len(got) == 1, (name, attrs, got)
    return got[0]


def _stream(engine, prompt, n_new, **kw):
    return list(engine.generate_stream(np.asarray(prompt, np.int32), n_new,
                                       temperature=0.0, **kw))


# ---------------------------------------------------------------------------
# the tracer: spans across threads, explicit parents, the ring's count
# ---------------------------------------------------------------------------


def test_open_on_one_thread_close_on_another(obs_on):
    sp = obs.open_span("serve.request", rid=7)
    assert obs_on.current_span() is None     # joins no thread's stack
    seen = {}

    def worker():
        with obs.span("child", parent=sp) as c:
            seen["parent_id"] = c.parent_id
        obs.record_span("waited", 0.25, parent=sp.span_id, rid=7)
        obs.close_span(sp)

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert seen["parent_id"] == sp.span_id
    req = _one(obs_on, "serve.request")
    assert req["attrs"]["rid"] == 7 and req["duration_s"] is not None
    assert _one(obs_on, "child")["parent_id"] == req["span_id"]
    waited = _one(obs_on, "waited")
    assert waited["parent_id"] == req["span_id"]
    assert waited["duration_s"] == pytest.approx(0.25, abs=1e-5)
    obs.close_span(sp)                       # closing twice records once
    assert len(obs_on.spans("serve.request")) == 1


def test_parent_defaults_to_this_threads_open_span(obs_on):
    with obs.span("outer") as outer:
        detached = obs.open_span("detached")
        assert detached.parent_id == outer.span_id
        with obs.span("inner", parent=None) as inner:
            assert inner.parent_id == outer.span_id
    obs.close_span(detached)


def test_record_span_can_end_before_the_call(obs_on):
    import time

    obs.record_span("waited", 0.25, ago=0.5, rid=1)
    now = time.perf_counter()
    s = _one(obs_on, "waited")
    assert s["duration_s"] == pytest.approx(0.25, abs=1e-5)
    assert now - _end(s) == pytest.approx(0.5, abs=0.05)


def test_discarded_span_is_not_recorded(obs_on):
    with obs.span("serve.admit") as sp:
        assert obs_on.current_span() is sp
        sp.discard()
    assert obs_on.spans("serve.admit") == []
    assert obs_on.current_span() is None


def test_discarded_span_is_nobodys_parent(obs_on):
    """A span opened under a discarded one names the next span out: the
    discarded one will not be in the ring for a reader to find."""
    with obs.span("outer") as outer:
        with obs.span("serve.tick.plan") as plan:
            plan.discard()
            with obs.span("serve.idle"):
                pass
    with obs.span("serve.tick.plan") as plan:
        plan.discard()
        with obs.span("serve.idle"):
            pass
    under, alone = obs_on.spans("serve.idle")
    assert under["parent_id"] == outer.span_id
    assert alone["parent_id"] is None


def test_disabled_open_span_is_the_null_span():
    obs.set_enabled(False)
    try:
        sp = obs.open_span("serve.request", rid=1)
        assert sp is obs_trace.NULL_SPAN
        sp.set_attr("tokens", 3)
        sp.set_parent(5)
        sp.discard()
        obs.close_span(sp)
        with obs.span("x") as x:
            assert x is obs_trace.NULL_SPAN
    finally:
        obs.set_enabled(None)


def test_ring_counts_what_it_drops_and_exports_it():
    reg = MetricsRegistry()
    tr = obs.Tracer(capacity=4, registry=reg)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    assert len(tr.spans()) == 4
    assert tr.dropped == 6
    text = reg.render_prometheus()
    assert "dl4j_spans_dropped_total 6" in text
    assert "dl4j_span_seconds" in text
    tr.clear()
    assert tr.dropped == 0 and tr.spans() == []


def test_kept_spans_are_flat_records_the_collector_does_not_track(tmp_path):
    """A ring of dicts is a ring of containers for every collection to
    walk, and each one promoted brings the next full collection nearer
    (50 ms in a serving process). A kept span is one flat tuple, in the
    ring and in the journal; readers get the same dicts as ever."""
    import gc

    jr = obs.FlightRecorder(path=str(tmp_path / "j.jsonl"),
                            flush_interval_s=1e9)
    tr = obs.Tracer(capacity=64, journal=jr)
    jr.record("checkpoint", step=3)
    for i in range(8):
        with tr.span("serve.batch", kind="decode.paged", lanes=i) as sp:
            sp.set_attr("admits", 0)
    tr.record_span("serve.queue", 0.25, parent=5, rid=9, slo=None)
    gc.collect(0)       # the first pass over the young objects untracks
    assert not any(gc.is_tracked(r) for r in tr._ring)
    assert not any(gc.is_tracked(e) for e in jr._ring
                   if isinstance(e, tuple))
    last = tr.spans("serve.batch")[-1]
    assert last["attrs"] == {"kind": "decode.paged", "lanes": 7, "admits": 0}
    assert set(last) == {"name", "span_id", "parent_id", "t_wall", "t_mono",
                         "duration_s", "attrs"}
    q = tr.spans("serve.queue")[0]
    assert q["parent_id"] == 5 and q["attrs"] == {"rid": 9, "slo": None}
    events = obs.FlightRecorder.load(jr.flush(fsync=True))
    assert [e["kind"] for e in events] == ["checkpoint"] + ["span"] * 9
    assert [e["seq"] for e in events] == list(range(1, 11))
    assert events[-1]["name"] == "serve.queue"
    assert events[-1]["attrs"] == {"rid": 9, "slo": None}
    assert jr.events("span")[0]["attrs"]["lanes"] == 0


@pytest.mark.parametrize("value, capacity", [
    (None, 65536), ("128", 128), ("many", 65536), ("", 65536)])
def test_default_ring_holds_a_window_of_fast_ticks(monkeypatch, value,
                                                   capacity):
    """65,536 unless the environment says otherwise; a value that is no
    number falls back to it and never leaves the ring unbounded (it would
    then count no drop)."""
    if value is None:
        monkeypatch.delenv("DL4J_TPU_OBS_SPANS", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_OBS_SPANS", value)
    assert obs.Tracer()._ring.maxlen == capacity


# ---------------------------------------------------------------------------
# the request's tree
# ---------------------------------------------------------------------------


def test_streamed_request_yields_one_tree_with_one_rid(obs_on, lm):
    engine = ServingEngine(model=lm)
    try:
        tokens = _stream(engine, [2, 4, 6, 8, 1, 3], 5)
    finally:
        engine.stop()
    assert len(tokens) == 5
    req = _one(obs_on, "serve.request", kind="generate_stream")
    rid = req["attrs"]["rid"]
    assert req["attrs"]["tokens"] == 5
    assert 0 < req["attrs"]["ttft_s"] <= req["duration_s"]

    queue = _one(obs_on, "serve.queue")
    admit = _one(obs_on, "serve.admit")
    dispatch = _one(obs_on, "serve.admit.dispatch")
    for s in (queue, admit):
        assert s["parent_id"] == req["span_id"]
        assert s["attrs"]["rid"] == rid
        assert _inside(s, req)
    assert dispatch["parent_id"] == admit["span_id"]
    assert _inside(dispatch, admit)
    assert queue["attrs"]["requeued"] == 0
    assert queue["attrs"]["pending"] == 0
    assert admit["attrs"]["prompt_tokens"] == 6
    assert admit["attrs"]["width"] == dispatch["attrs"]["width"] >= 6
    a = admit["attrs"]
    assert a["hit_blocks"] + a["fresh_blocks"] == a["lookup_blocks"] + 1
    # the queue wait ends where the pick ends, inside the admission
    assert admit["t_mono"] - EPS <= _end(queue) <= _end(admit) + EPS

    # the request closes after its last token, which the last tick gave
    ticks = [s for s in obs_on.spans("serve.batch")
             if s["attrs"]["kind"] == "decode.paged"]
    assert len(ticks) == 5
    assert _end(req) >= max(_end(s) for s in ticks) - EPS
    first = min(ticks, key=lambda s: s["t_mono"])
    assert req["attrs"]["ttft_s"] >= _end(first) - req["t_mono"] - EPS


def test_tick_phases_nest_and_count_admissions(obs_on, lm):
    engine = ServingEngine(model=lm)
    try:
        decoder = engine._decoder_for(engine.registry.get(None, None))
        futs = [decoder.submit([1 + i, 2, 3], 4, temperature=0.0)
                for i in range(3)]
        for f in futs:
            f.result(timeout=240)
    finally:
        engine.stop()
    ticks = sorted((s for s in obs_on.spans("serve.batch")
                    if s["attrs"]["kind"] == "decode.paged"),
                   key=lambda s: s["t_mono"])
    stages = {s["parent_id"]: s for s in obs_on.spans("serve.tick.stage")}
    waits = {s["parent_id"]: s for s in obs_on.spans("serve.tick.wait")}
    emits = {s["attrs"]["tick"]: s for s in obs_on.spans("serve.tick.emit")}
    admits = sorted(obs_on.spans("serve.admit"), key=lambda s: s["t_mono"])
    assert len(admits) == 3
    assert {len(stages), len(waits), len(emits)} == {len(ticks)}
    prev_end = 0.0
    for t in ticks:
        st, wt, em = (d[t["span_id"]] for d in (stages, waits, emits))
        assert _inside(st, t) and _inside(wt, t)
        assert _end(st) <= wt["t_mono"] + EPS
        assert em["t_mono"] >= _end(t) - EPS      # a sibling, after it
        assert em["parent_id"] == t["parent_id"]
        since = [a for a in admits
                 if prev_end - EPS <= a["t_mono"] and _end(a) <= t["t_mono"] + EPS]
        assert t["attrs"]["admits"] == len(since)
        assert t["attrs"]["admit_width_sum"] == sum(
            a["attrs"]["width"] for a in since)
        prev_end = _end(t)
    assert sum(t["attrs"]["admits"] for t in ticks) == 3
    # the rest of the worker's pass has names too: the sweep at its head
    # and the tick's scheduling decision, each before the tick it precedes
    plans = sorted(obs_on.spans("serve.tick.plan"), key=lambda s: s["t_mono"])
    sweeps = obs_on.spans("serve.sweep")
    assert len(plans) == len(ticks) <= len(sweeps)
    for p, t in zip(plans, ticks):
        assert _end(p) <= t["t_mono"] + EPS
    # requests submitted with no span open: spans all the same, no rid
    assert {a["attrs"]["rid"] for a in admits} == {None}
    assert {a["parent_id"] for a in admits} == {None}


def test_unary_generate_parents_through_the_open_span(obs_on, lm):
    engine = ServingEngine(model=lm)
    try:
        engine.generate(np.asarray([3, 1, 4, 1, 5], np.int32), 3,
                        temperature=0.0)
    finally:
        engine.stop()
    req = _one(obs_on, "serve.request", kind="generate")
    admit = _one(obs_on, "serve.admit")
    assert admit["parent_id"] == req["span_id"]
    assert admit["attrs"]["rid"] == req["attrs"]["rid"]
    assert _one(obs_on, "serve.queue")["attrs"]["rid"] == req["attrs"]["rid"]


def test_preempted_request_keeps_its_rid(obs_on, lm):
    from deeplearning4j_tpu.serving.paged import PagedDecoder

    # 7 blocks of 8 tokens cannot hold three 23-token sequences at once:
    # growth preempts the youngest, which queues and is admitted again
    d = PagedDecoder(lm, block_tokens=8, n_blocks=7)
    parents = []
    try:
        futs = []
        for i, p in enumerate(([2, 4, 6], [1, 1, 1, 1], [9, 8, 7])):
            sp = obs.open_span("serve.request", rid=100 + i)
            parents.append(sp)
            futs.append(d.submit(p, 20, temperature=0.0, parent=sp))
        for f in futs:
            f.result(timeout=240)
        assert d.stats.preemptions >= 1
    finally:
        d.stop()
        for sp in parents:
            obs.close_span(sp)
    by_parent = {sp.span_id: sp.attrs["rid"] for sp in parents}
    queues = obs_on.spans("serve.queue")
    admits = obs_on.spans("serve.admit")
    assert len(queues) == len(admits) == 3 + d.stats.preemptions
    for s in queues + admits:
        assert s["attrs"]["rid"] == by_parent[s["parent_id"]]
    again = [s for s in queues if s["attrs"]["requeued"] >= 1]
    assert len(again) == d.stats.preemptions
    # the second wait starts at the preemption, not at the first enqueue
    for s in again:
        first = next(q for q in queues if q["attrs"]["rid"] == s["attrs"]["rid"]
                     and q["attrs"]["requeued"] == 0)
        assert s["t_mono"] >= _end(first) - EPS


def test_idle_pool_is_a_named_span(obs_on, lm):
    engine = ServingEngine(model=lm)
    try:
        _stream(engine, [5, 5, 5], 2)
        _stream(engine, [6, 6, 6], 2)
    finally:
        engine.stop()
    idle = obs_on.spans("serve.idle")
    assert idle, "the wait for a request between the two is serve.idle"
    # the plan that found nothing was discarded, and is not their parent
    assert all(s["parent_id"] is None for s in idle)
    # a pass that found the pool empty planned nothing: one plan a tick
    ticks = [s for s in obs_on.spans("serve.batch")
             if s["attrs"]["kind"] == "decode.paged"]
    assert len(obs_on.spans("serve.tick.plan")) == len(ticks) == 4


def test_tracing_off_records_nothing_and_streams_the_same(lm):
    def run():
        engine = ServingEngine(model=lm)
        try:
            return (_stream(engine, [2, 4, 6, 8], 8),
                    list(engine.generate_stream(
                        np.asarray([7, 7, 1], np.int32), 6,
                        temperature=0.9, seed=3)))
        finally:
            engine.stop()

    obs.set_enabled(False)
    try:
        obs.tracer().clear()
        off = run()
        assert obs.tracer().spans() == []
        obs.set_enabled(True)
        on = run()
        assert obs.tracer().spans("serve.request")
    finally:
        obs.set_enabled(None)
        obs.tracer().clear()
    assert np.asarray(off[0], np.int32).tobytes() == \
        np.asarray(on[0], np.int32).tobytes()
    assert np.asarray(off[1], np.int32).tobytes() == \
        np.asarray(on[1], np.int32).tobytes()


def test_failed_submit_closes_the_request_span(obs_on, lm):
    engine = ServingEngine(model=lm)
    try:
        with pytest.raises(ValueError):
            engine.generate_stream(np.asarray([1, 2], np.int32), 10 ** 6)
    finally:
        engine.stop()
    req = _one(obs_on, "serve.request", kind="generate_stream")
    assert req["attrs"]["error"] == "ValueError"
    assert req["duration_s"] is not None


def test_stream_never_started_still_closes_the_request_span(obs_on, lm):
    """A generator nobody iterates never runs its ``finally``: the request
    span closes when the generator is let go, so the queue and admission
    spans under it name a parent that is in the ring."""
    import gc

    engine = ServingEngine(model=lm)
    try:
        it = engine.generate_stream(np.asarray([3, 1, 4], np.int32), 3,
                                    temperature=0.0)
        engine.drain(60)
        assert obs_on.spans("serve.request") == []
        del it
        gc.collect()
    finally:
        engine.stop()
    req = _one(obs_on, "serve.request", kind="generate_stream")
    assert req["duration_s"] is not None and "ttft_s" in req["attrs"]
    admit = _one(obs_on, "serve.admit")
    assert admit["parent_id"] == req["span_id"]


# ---------------------------------------------------------------------------
# one clock: the profiler's host plane holds the program's span names
# ---------------------------------------------------------------------------


def test_profiler_host_plane_holds_the_span_names(obs_on, lm, tmp_path):
    import jax
    from jax.profiler import ProfileData

    engine = ServingEngine(model=lm)
    try:
        _stream(engine, [2, 4, 6], 2)            # compile outside the trace
        logdir = str(tmp_path / "trace")
        jax.profiler.start_trace(logdir)
        try:
            _stream(engine, [3, 5, 7, 9], 4)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.stop()
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files
    names = set()
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    for want in ("serve.admit", "serve.admit.dispatch", "serve.batch",
                 "serve.tick.stage", "serve.tick.wait", "serve.tick.emit",
                 "serve.tick.upload", "serve.tick.dispatch",
                 "serve.tick.read_keys"):
        assert want in names, (want, sorted(n for n in names
                                            if n.startswith("serve")))
    # a wait recorded after the fact and a span that crosses threads are
    # not host work: no annotation
    assert "serve.queue" not in names and "serve.request" not in names


def test_delivery_runs_under_the_next_dispatch(obs_on, lm):
    """``serve.tick.deliver`` (callbacks and futures) of a tick that leaves
    lanes live lies inside the NEXT tick, after its dispatch and before its
    wait; the tick that empties the pool delivers inside its own emit."""
    engine = ServingEngine(model=lm)
    try:
        assert len(_stream(engine, [5, 1, 4], 4)) == 4
    finally:
        engine.stop()
    by_start = lambda spans: sorted(spans, key=lambda s: s["t_mono"])
    ticks = by_start(s for s in obs_on.spans("serve.batch")
                     if s["attrs"]["kind"] == "decode.paged")
    delivers = by_start(obs_on.spans("serve.tick.deliver"))
    assert len(ticks) == len(delivers) == 4
    assert [d["attrs"]["tokens"] for d in delivers] == [1, 1, 1, 1]
    stages = {s["parent_id"]: s for s in obs_on.spans("serve.tick.stage")}
    waits = {s["parent_id"]: s for s in obs_on.spans("serve.tick.wait")}
    for d, t in zip(delivers[:3], ticks[1:]):
        assert d["parent_id"] == t["span_id"]
        assert _end(stages[t["span_id"]]) <= d["t_mono"] + EPS
        assert _end(d) <= waits[t["span_id"]]["t_mono"] + EPS
    last_emit = by_start(obs_on.spans("serve.tick.emit"))[-1]
    assert last_emit["attrs"]["tick"] == ticks[-1]["span_id"]
    assert delivers[-1]["parent_id"] == last_emit["span_id"]


def test_stage_is_upload_then_dispatch_and_keys_are_read_in_emit(obs_on, lm):
    """Every tick's ``serve.tick.stage`` holds exactly one
    ``serve.tick.upload`` (the inputs built and sent) and then one
    ``serve.tick.dispatch`` (the jitted call alone, whose end is where the
    program is on the device's queue); the keys' readback
    ``serve.tick.read_keys`` lies inside the tick's ``serve.tick.emit``."""
    engine = ServingEngine(model=lm)
    try:
        decoder = engine._decoder_for(engine.registry.get(None, None))
        futs = [decoder.submit([3 + i, 1, 4], 3 + i, temperature=0.0)
                for i in range(3)]
        for f in futs:
            f.result(timeout=240)
    finally:
        engine.stop()
    ticks = [s for s in obs_on.spans("serve.batch")
             if s["attrs"]["kind"] == "decode.paged"]
    stages = {s["span_id"]: s for s in obs_on.spans("serve.tick.stage")}
    assert len(stages) == len(ticks) >= 5
    assert {s["parent_id"] for s in stages.values()} == \
        {t["span_id"] for t in ticks}
    children = {sid: [] for sid in stages}
    for name in ("serve.tick.upload", "serve.tick.dispatch"):
        for s in obs_on.spans(name):
            children[s["parent_id"]].append(s)
    for sid, kids in children.items():
        kids.sort(key=lambda s: s["t_mono"])
        assert [k["name"] for k in kids] == ["serve.tick.upload",
                                             "serve.tick.dispatch"]
        up, disp = kids
        assert _inside(up, stages[sid]) and _inside(disp, stages[sid])
        assert _end(up) <= disp["t_mono"] + EPS
    emits = {s["span_id"]: s for s in obs_on.spans("serve.tick.emit")}
    reads = obs_on.spans("serve.tick.read_keys")
    assert len(reads) == len(emits) == len(ticks)
    for r in reads:
        assert _inside(r, emits[r["parent_id"]])


def test_streamed_request_times_its_last_token(obs_on, lm):
    """``last_token_s`` is the newest token's time from the request's
    open, set where ``ttft_s`` is: the stream ends after it, so the span
    outlasts it by the stream's own tail."""
    engine = ServingEngine(model=lm)
    try:
        assert len(_stream(engine, [2, 7, 1, 8], 6)) == 6
        assert len(_stream(engine, [2, 8], 1)) == 1
    finally:
        engine.stop()
    reqs = sorted(obs_on.spans("serve.request"), key=lambda s: s["t_mono"])
    assert len(reqs) == 2
    for req in reqs:
        a = req["attrs"]
        assert 0 < a["ttft_s"] <= a["last_token_s"] <= req["duration_s"] + EPS
    many, one = reqs
    assert many["attrs"]["ttft_s"] < many["attrs"]["last_token_s"]
    assert one["attrs"]["ttft_s"] == one["attrs"]["last_token_s"]
    # the last token came from the request's last tick
    ticks = [s for s in obs_on.spans("serve.batch")
             if s["attrs"]["kind"] == "decode.paged"
             and _inside(s, many)]
    assert many["t_mono"] + many["attrs"]["last_token_s"] >= \
        max(_end(t) for t in ticks) - EPS
