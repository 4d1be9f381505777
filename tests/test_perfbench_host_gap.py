"""The serve tick's host gap split by cause (perfbench/host_gap.py and the
five `idle_*` readers that call it) and the stream's tail after its last
token (`stream_tail_p50_ms.serve`), each over hand-built device intervals,
host events and spans: a gap with no dispatch inside it, with one, with
two; nested spans where the innermost wins; a device clock off the host's;
the traced tail's edges; the sum rule against `device_idle.serve`; and no number from a program without
the spans. Then one toy serve run on the CPU with the new metrics listed,
which shows the stream's reader against the program's real ring. No
number here is a measurement.

Reference anchor: none in the reference (it has no serving benchmark); the
split's definition is PERF.md section 3.
"""
import json
import os
import time

import pytest

from deeplearning4j_tpu.obs import trace as obs_trace
from perfbench import harness, host_gap, run, trace_reduce

BASE = os.path.join(harness.HERE, "tests", "data")
SERVE_CELLS = ("serve-590m-chat", "serve-granite-h-micro-chat",
               "serve-smallthinker-stage12-mixed",
               "serve-qwen3-next-stage8-longdoc",
               "serve-phi4-mini-flash-agent16k")
IDLE = {"idle_after_dispatch.serve": host_gap.AFTER_DISPATCH,
        "idle_in_tick_upload.serve": "upload",
        "idle_in_tick_dispatch.serve": "dispatch",
        "idle_in_tick_readback.serve": "readback",
        "idle_in_tick_booking.serve": "booking"}
TAIL = "stream_tail_p50_ms.serve"
NEW = tuple(IDLE) + (TAIL,)


def reader(name):
    return harness.load_reader(name).read


class FakeTrace:
    def __init__(self, ops, host):
        self.device_ops = {0: ops} if ops else {}
        self.host = host

    def busy_s(self):
        return trace_reduce.busy_union(
            (s, e) for s, e, _ in self.device_ops.get(0, []))

    def idle_percent(self, window_s):
        busy = self.busy_s()
        return 100.0 * (1.0 - busy / window_s) if busy > 0 else None


# the device busy 0-10, 14-20, 23-30, 33-40, 41-50 of a 52 s tail: gaps
# 10-14 (one dispatch inside), 20-23 (two), 30-33 (none), 40-41 (one, part
# of it under no span); 2 s of edges
OPS = [(0.0, 10.0, "a"), (14.0, 20.0, "b"), (23.0, 30.0, "c"),
       (33.0, 40.0, "d"), (41.0, 50.0, "e")]
HOST = [
    # gap 10-14: the readback, the emit around the keys' read, the sweep,
    # the plan, then a tick's upload and dispatch (its point at 13.5) and
    # the last tick's delivery under it
    (5.0, 10.5, "serve.tick.wait"),
    (10.5, 12.0, "serve.tick.emit"), (10.5, 11.0, "serve.tick.read_keys"),
    (12.0, 12.5, "serve.sweep"), (12.5, 12.8, "serve.tick.plan"),
    (12.8, 20.5, "serve.batch"), (12.8, 13.6, "serve.tick.stage"),
    (12.8, 13.2, "serve.tick.upload"), (13.2, 13.5, "serve.tick.dispatch"),
    (13.6, 14.5, "serve.tick.deliver"), (14.5, 20.5, "serve.tick.wait"),
    # gap 20-23: an admission dispatched at 21.8, then a tick at 22.5
    (20.5, 21.0, "serve.tick.emit"), (20.5, 20.7, "serve.tick.read_keys"),
    (21.0, 22.0, "serve.admit"), (21.5, 21.8, "serve.admit.dispatch"),
    (22.0, 22.2, "serve.tick.plan"),
    (22.2, 31.0, "serve.batch"), (22.2, 22.6, "serve.tick.stage"),
    (22.2, 22.4, "serve.tick.upload"), (22.4, 22.5, "serve.tick.dispatch"),
    (22.6, 31.0, "serve.tick.wait"),
    # gap 30-33: nothing dispatched inside it
    (31.0, 32.0, "serve.tick.emit"),
    # gap 40-41: waiting for a request, nothing, a dispatch at 40.8
    (39.0, 40.3, "serve.idle"), (40.6, 40.8, "serve.tick.dispatch"),
    # what is not the program's own is left out
    (0.0, 52.0, "DevicePutWithSharding"), (10.0, 14.0, "np.asarray"),
]
WINDOW_S = 52.0
SECONDS = {"after_dispatch": 0.5 + 1.2 + 3.0 + 0.2, "upload": 0.4,
           "dispatch": 0.3 + 0.2, "readback": 0.5 + 0.5 + 0.5 + 0.2,
           "booking": 1.0 + 0.5 + 0.3 + 0.3, "admit": 0.8, "idle": 0.3,
           "none": 0.3 + 2.0}


def _ctx(ops=OPS, host=HOST):
    return {"trace": FakeTrace(list(ops), list(host)),
            "traced": {"window_s": WINDOW_S}}


def _gap(s, e):
    return host_gap.split([(s, e)], HOST)


def test_a_gap_no_dispatch_call_overlaps_is_all_after_dispatch():
    got = _gap(30.0, 33.0)
    assert got["after_dispatch"] == pytest.approx(3.0)
    assert sum(got.values()) == pytest.approx(3.0)


def test_a_gap_splits_at_its_one_dispatch_point():
    got = _gap(10.0, 14.0)
    assert got["after_dispatch"] == pytest.approx(0.5)
    # the wait and the keys' read inside the emit are the readback; the
    # emit's own part, the sweep and the plan the booking
    assert got["readback"] == pytest.approx(0.5 + 0.5)
    assert got["booking"] == pytest.approx(1.0 + 0.5 + 0.3)
    assert got["upload"] == pytest.approx(0.4)
    assert got["dispatch"] == pytest.approx(0.3)
    assert sum(got.values()) == pytest.approx(4.0)


def test_a_gap_with_two_dispatches_splits_at_the_first():
    got = _gap(20.0, 23.0)
    # the admission's dispatch at 21.8 ends the host's part; the tick's
    # upload and dispatch after it are already the queue's
    assert got["admit"] == pytest.approx(0.8)
    assert got["after_dispatch"] == pytest.approx(1.2)
    assert got["upload"] == got["dispatch"] == 0.0
    assert got["readback"] == pytest.approx(0.5 + 0.2)
    assert got["booking"] == pytest.approx(0.3)


def test_a_stretch_under_no_span_before_the_dispatch_is_none():
    got = _gap(40.0, 41.0)
    assert got["idle"] == pytest.approx(0.3)
    assert got["none"] == pytest.approx(0.3)
    assert got["dispatch"] == pytest.approx(0.2)
    assert got["after_dispatch"] == pytest.approx(0.2)


def test_a_call_still_open_at_the_gap_end_is_its_dispatch_point():
    """The device may start a program before the call that hands it over
    returns (the call still wraps its outputs): the gap is then the
    host's up to its end, and none of it is after the dispatch."""
    host = [(0.0, 5.0, "serve.tick.emit"), (5.0, 6.0, "serve.tick.plan"),
            (6.0, 9.0, "serve.batch"), (6.0, 8.5, "serve.tick.stage"),
            (6.0, 7.5, "serve.tick.upload"),
            (7.5, 8.5, "serve.tick.dispatch")]
    got = host_gap.split([(4.0, 8.0)], host)
    assert got["booking"] == pytest.approx(1.0 + 1.0)
    assert got["upload"] == pytest.approx(1.5)
    assert got["dispatch"] == pytest.approx(0.5)
    assert got["after_dispatch"] == 0.0
    # a gap inside the call: all of it the call's
    assert host_gap.split([(7.6, 8.2)], host)["dispatch"] == \
        pytest.approx(0.6)
    # a call that ended before the gap is not its dispatch point
    assert host_gap.split([(8.7, 9.0)], host)["after_dispatch"] == \
        pytest.approx(0.3)


def test_the_tail_adds_up_to_device_idle_with_its_edges_as_none():
    shares = host_gap.idle_by_class(_ctx())
    assert set(shares) == set(host_gap.CLASSES)
    for label, seconds in SECONDS.items():
        assert shares[label] == pytest.approx(100 * seconds / WINDOW_S), label
    device_idle = reader("device_idle.serve")(_ctx())
    assert sum(shares.values()) == pytest.approx(device_idle, abs=1e-9)
    for name, label in IDLE.items():
        assert reader(name)(_ctx()) == pytest.approx(shares[label])


# the runtime's host event that puts each program of OPS on the device's
# queue, ending where the device starts it (the program of 33 s was queued
# long before: it ends no wait)
ENQUEUED = [(13.95, 14.0, "DoEnqueueProgram"), (22.95, 23.0, "DoEnqueueProgram"),
            (28.0, 28.1, "DoEnqueueProgram"), (40.95, 41.0, "DoEnqueueProgram")]


@pytest.mark.parametrize("early", [0.0, 0.4, 1.3])
def test_the_device_clock_is_laid_on_the_hosts_by_the_enqueue(early):
    """A profile whose device runs `early` seconds ahead of the host's clock
    splits as the aligned one does: the median over the gaps of the nearest
    enqueue's end less the gap's end is added to the device's times."""
    ops = [(s - early, e - early, n) for s, e, n in OPS]
    ctx = _ctx(ops=ops, host=HOST + ENQUEUED)
    idle = trace_reduce.gaps((s, e) for s, e, _ in ops)
    assert host_gap.clock_offset(idle, HOST + ENQUEUED) == \
        pytest.approx(early)
    shares = host_gap.idle_by_class(ctx)
    for label, seconds in SECONDS.items():
        assert shares[label] == pytest.approx(100 * seconds / WINDOW_S), label


def test_no_enqueue_event_no_gap_of_the_hosts_no_shift():
    idle = trace_reduce.gaps((s, e) for s, e, _ in OPS)
    assert host_gap.clock_offset(idle, HOST) == 0.0
    assert host_gap.clock_offset([(1.0, 1.00005)], ENQUEUED) == 0.0


def test_the_innermost_span_wins():
    """Nested spans: the shortest open one names each stretch; a span's
    own part (what its children leave) is its own class."""
    host = [(0.0, 10.0, "serve.batch"), (1.0, 5.0, "serve.tick.stage"),
            (1.0, 3.0, "serve.tick.upload"), (3.5, 5.0, "serve.tick.dispatch"),
            (6.0, 9.0, "serve.tick.emit"), (6.0, 7.0, "serve.tick.read_keys"),
            (9.5, 9.8, "serve.admit.dispatch")]
    got = host_gap.split([(0.5, 4.0), (5.5, 10.0)], host)
    assert got["upload"] == pytest.approx(2.0)
    assert got["dispatch"] == pytest.approx(0.5)
    assert got["readback"] == pytest.approx(1.0)
    # the batch's own part, the stage's own, the batch's, the emit's, the
    # batch's
    assert got["booking"] == pytest.approx(0.5 + 0.5 + 0.5 + 2.0 + 0.5)
    assert got["admit"] == pytest.approx(0.3)
    assert got["after_dispatch"] == pytest.approx(0.2)


@pytest.mark.parametrize("name", tuple(IDLE))
def test_a_program_without_the_spans_gives_no_number(name):
    # the parent: the tick's stage, wait and emit, no dispatch span
    old = [ev for ev in HOST if ev[2] not in (
        "serve.tick.upload", "serve.tick.dispatch", "serve.tick.read_keys")]
    assert reader(name)(_ctx(host=old)) is None
    # PJRT's names alone
    assert reader(name)(_ctx(host=HOST[-2:])) is None
    # no operation traced (the CPU)
    assert reader(name)(_ctx(ops=[])) is None


# ---------------------------------------------------------------------------
# the stream's tail
# ---------------------------------------------------------------------------


def _span(name, start, dur, **attrs):
    return {"name": name, "span_id": None, "parent_id": None, "t_wall": 0.0,
            "t_mono": start, "duration_s": dur, "attrs": attrs}


class FakeTracer:
    def __init__(self, spans, dropped=0):
        self._spans, self.dropped = spans, dropped

    def spans(self, name=None):
        return [s for s in self._spans if name is None or s["name"] == name]


@pytest.fixture
def ring(monkeypatch):
    def put(spans, dropped=0):
        t = FakeTracer(spans, dropped)
        monkeypatch.setattr(obs_trace, "tracer", lambda: t)
    return put


def _window():
    ticks = [_span("serve.batch", 10.0 + i, 0.1, kind="decode.paged")
             for i in range(10)]
    # tails of 150..250 ms inside the window; one before it, one after its
    # last tick, one unfinished and one that never streamed a token
    reqs = [_span("serve.request", 10.0 + 0.5 * i, 1.0 + 0.05 * i,
                  ttft_s=0.1, last_token_s=1.0 + 0.05 * i - 0.15 - 0.01 * i)
            for i in range(11)]
    reqs += [_span("serve.request", 9.0, 2.0, ttft_s=0.1, last_token_s=0.1),
             _span("serve.request", 19.5, 2.0, ttft_s=0.1, last_token_s=0.1),
             _span("serve.request", 12.0, None, ttft_s=0.1),
             _span("serve.request", 12.0, 0.3, error="ValueError")]
    return ticks, reqs


def test_stream_tail_is_the_median_from_last_token_to_close(ring):
    ticks, reqs = _window()
    ring(ticks + reqs)
    assert reader(TAIL)({"spans": ticks, "t0": 9.5}) == pytest.approx(200.0)


def test_stream_tail_gives_no_number_without_the_attribute_or_the_ring(ring):
    ticks, reqs = _window()
    ring(ticks + reqs, dropped=1)
    assert reader(TAIL)({"spans": ticks, "t0": 9.5}) is None
    # the parent: request spans with `ttft_s` alone
    for r in reqs:
        r["attrs"].pop("last_token_s", None)
    ring(ticks + reqs)
    assert reader(TAIL)({"spans": ticks, "t0": 9.5}) is None
    assert reader(TAIL)({"spans": [], "t0": 9.5}) is None


# ---------------------------------------------------------------------------
# the manifest, and the readers against the program's own ring
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_manifest_lists_the_new_metric_for_the_serve_cells(name):
    rows = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    m = rows[name]
    assert tuple(m["workloads"]) == SERVE_CELLS
    assert m["moves"] == "serve_tokens_per_s"
    assert m["layer"] == ("engine" if name == TAIL else "device")
    assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                       name + ".py"))


def test_toy_serve_run_reports_the_stream_tail():
    """A toy traced serve run on the CPU with the new metrics listed for the
    toy cell: the stream's tail is read from the program's ring (each
    stream ends on the engine's poll after its last token); the idle shares
    need device operations, which the CPU's trace has none of."""
    manifest = harness.load_json(os.path.join(BASE, "BENCHMARK.json"))
    listed = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    manifest["per_layer"] += [dict(listed[n], workloads=["tiny-serve"])
                              for n in NEW]
    result = run.run_cell("tiny-serve", 2**31 + 39, 2.0, True,
                          manifest=manifest, base=BASE,
                          t_start=time.perf_counter())
    line = json.loads(json.dumps(result))
    assert line["correct"] is True
    got = line["metrics"]
    assert got[TAIL]["unit"] == "ms"
    assert 0 < got[TAIL]["value"] < 1e3
    assert not set(IDLE) & set(got)
