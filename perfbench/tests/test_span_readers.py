"""The readers of the program's serving spans (perfbench/span_reduce.py and
the seven perfbench/metrics/*.serve.py that call it), each over hand-built
spans, host events and device intervals: the window's cut, the dropped-spans
case, a program without the spans, the split of idle gaps by phase and its
sum rule. Then one toy serve run on the CPU with the metrics listed, which
shows the readers against the program's real ring. No number here is a
measurement.
"""
import json
import os
import time

import pytest

from deeplearning4j_tpu.obs import trace as obs_trace
from perfbench import harness, run, span_reduce as sr

BASE = os.path.join(harness.HERE, "tests", "data")
NEW = ("queue_wait_p95_ms.serve", "admit_host_p50_ms.serve",
       "prefill_per_admit_ms.serve", "tick_host_p50_ms.serve",
       "idle_in_admit.serve", "idle_in_tick_host.serve",
       "idle_unattributed.serve")


def reader(name):
    return harness.load_reader(name).read


def _span(name, start, dur, span_id=None, parent=None, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent,
            "t_wall": 0.0, "t_mono": start, "duration_s": dur,
            "attrs": attrs}


class FakeTracer:
    def __init__(self, spans, dropped=0):
        self._spans, self.dropped = spans, dropped

    def spans(self, name=None):
        return [s for s in self._spans if name is None or s["name"] == name]


class OldTracer:
    """The tracer of a program from before these spans: no `dropped`."""

    def spans(self, name=None):
        return []


@pytest.fixture
def ring(monkeypatch):
    def put(spans, dropped=0, tracer=None):
        t = tracer or FakeTracer(spans, dropped)
        monkeypatch.setattr(obs_trace, "tracer", lambda: t)
    return put


# ten ticks of 100 ms from t = 10; ticks 3 and 7 wait out 2 and 1 prefills
def _ticks():
    out = []
    for i in range(10):
        admits = {3: 2, 7: 1}.get(i, 0)
        out.append(_span("serve.batch", 10.0 + i, 0.100 + 0.020 * admits,
                         span_id=100 + i, kind="decode.paged",
                         admits=admits, admit_width_sum=256 * admits))
    return out


def _ring_spans(ticks):
    spans = list(ticks)
    for t in ticks:
        spans.append(_span("serve.tick.stage", t["t_mono"], 0.002,
                           parent=t["span_id"]))
        spans.append(_span("serve.tick.emit",
                           t["t_mono"] + t["duration_s"], 0.001,
                           tick=t["span_id"]))
    # queue waits 1..20 ms and admissions of 3 ms: the first four before the
    # window's first tick (the opening burst, which counts), the rest inside
    # it; and one of each after the window's last tick, which must not count
    for i in range(20):
        spans.append(_span("serve.queue", 9.9 + 0.4 * i, 0.001 * (i + 1)))
        spans.append(_span("serve.admit", 9.9 + 0.4 * i, 0.003))
    spans.append(_span("serve.queue", 19.5, 5.0))
    spans.append(_span("serve.admit", 19.5, 5.0))
    spans.append(_span("serve.tick.stage", 19.5, 5.0, parent=99))
    return spans


def test_program_span_readers_over_a_hand_built_window(ring):
    ticks = _ticks()
    ring(_ring_spans(ticks))
    ctx = {"spans": ticks}
    assert reader("queue_wait_p95_ms.serve")(ctx) == pytest.approx(19.05)
    assert reader("admit_host_p50_ms.serve")(ctx) == pytest.approx(3.0)
    # ticks with admissions: (140 - 100) / 2 and (120 - 100) / 1
    assert reader("prefill_per_admit_ms.serve")(ctx) == pytest.approx(20.0)
    assert reader("tick_host_p50_ms.serve")(ctx) == pytest.approx(3.0)


@pytest.mark.parametrize("name", NEW[:4])
def test_dropped_spans_give_no_number(ring, name):
    ticks = _ticks()
    ring(_ring_spans(ticks), dropped=1)
    assert reader(name)({"spans": ticks}) is None


@pytest.mark.parametrize("name", NEW[:4])
def test_a_program_without_the_spans_gives_no_number(ring, name):
    # the parent: serve.batch without `admits`, a tracer without `dropped`
    old = [_span("serve.batch", 10.0 + i, 0.1, kind="decode.paged")
           for i in range(5)]
    ring([], tracer=OldTracer())
    assert reader(name)({"spans": old}) is None
    assert reader(name)({"spans": []}) is None
    # these spans' tracer, but the spans themselves absent
    ring(old)
    assert reader(name)({"spans": old}) is None


def test_prefill_per_admit_needs_both_kinds_of_tick(ring):
    ticks = [t for t in _ticks() if t["attrs"]["admits"] == 0]
    ring(ticks)
    assert reader("prefill_per_admit_ms.serve")({"spans": ticks}) is None


def test_label_segments_innermost_wins_and_joins_neighbours():
    events = [(0.0, 10.0, "admit"), (2.0, 4.0, "admit"),   # child, same phase
              (12.0, 20.0, "tick"), (13.0, 14.0, "tick"),
              (11.0, 30.0, "other"),                        # a longer span around
              (40.0, 40.0, "empty")]
    assert sr.label_segments(events) == [
        (0.0, 10.0, "admit"), (11.0, 12.0, "other"), (12.0, 20.0, "tick"),
        (20.0, 30.0, "other")]


def test_split_gaps_by_overlap():
    segments = [(0.0, 10.0, "admit"), (12.0, 20.0, "tick")]
    idle = [(1.0, 2.0), (9.0, 13.0), (19.0, 25.0), (30.0, 31.0)]
    got = sr.split_gaps(idle, segments)
    assert got["admit"] == pytest.approx(1.0 + 1.0)
    assert got["tick"] == pytest.approx(1.0 + 1.0)
    assert got[sr.UNATTRIBUTED] == pytest.approx(2.0 + 5.0 + 1.0)
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in idle))


class FakeTrace:
    def __init__(self, ops, host):
        self.device_ops = {0: ops} if ops else {}
        self.host = host

    def busy_s(self):
        from perfbench import trace_reduce
        return trace_reduce.busy_union((s, e) for s, e, _ in
                                       self.device_ops.get(0, []))

    def idle_percent(self, window_s):
        busy = self.busy_s()
        return 100.0 * (1.0 - busy / window_s) if busy > 0 else None


def _traced_ctx():
    # device busy 0-40, 45-80, 82-90, 96-100 of a 100 s tail, whose first
    # second and last second lie outside the operations (window 102 s)
    ops = [(0.0, 40.0, "a"), (45.0, 80.0, "b"), (82.0, 90.0, "c"),
           (96.0, 100.0, "d")]
    host = [(39.0, 44.0, "serve.admit"), (41.0, 43.0, "serve.admit.dispatch"),
            (44.5, 81.0, "serve.batch"), (44.5, 45.5, "serve.tick.stage"),
            (45.5, 81.0, "serve.tick.wait"), (81.0, 83.0, "serve.tick.emit"),
            (90.5, 95.0, "serve.idle"),
            (0.0, 100.0, "DevicePutWithSharding"), (40.0, 41.0, "sha256")]
    return {"trace": FakeTrace(ops, host), "traced": {"window_s": 102.0}}


def test_idle_is_split_by_phase_and_adds_up_to_device_idle():
    ctx = _traced_ctx()
    split = sr.idle_by_phase(ctx)
    # gaps: 40-45 (admit 40-44, none 44-44.5, tick 44.5-45), 80-82 (tick),
    # 90-96 (none .5, idle 4.5, none 1); edges 2 s
    assert split["admit"] == pytest.approx(100 * 4.0 / 102)
    assert split["tick"] == pytest.approx(100 * 2.5 / 102)
    assert split["idle"] == pytest.approx(100 * 4.5 / 102)
    assert split[sr.UNATTRIBUTED] == pytest.approx(100 * (2.0 + 2.0) / 102)
    device_idle = reader("device_idle.serve")(ctx)
    assert sum(split.values()) == pytest.approx(device_idle)
    assert reader("idle_in_admit.serve")(ctx) == pytest.approx(split["admit"])
    assert reader("idle_in_tick_host.serve")(ctx) == \
        pytest.approx(split["tick"])
    assert reader("idle_unattributed.serve")(ctx) == \
        pytest.approx(split[sr.UNATTRIBUTED])


@pytest.mark.parametrize("name", NEW[4:])
def test_idle_readers_need_device_operations_and_annotations(name):
    ctx = _traced_ctx()
    # a program whose spans are no annotations: PJRT's names alone
    ctx["trace"].host = [ev for ev in ctx["trace"].host
                         if not ev[2].startswith("serve.")]
    assert reader(name)(ctx) is None
    # no operation traced (the CPU)
    assert reader(name)({"trace": FakeTrace([], _traced_ctx()["trace"].host),
                         "traced": {"window_s": 3.0}}) is None


def test_manifest_lists_the_new_metrics_for_serve_cells_only():
    manifest = harness.load_manifest()
    rows = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        # found by name: later PRs append to the manifest and list further
        # cells, so neither the place nor the whole list is pinned
        assert "serve-590m-chat" in rows[name]["workloads"]
        assert not any(w.startswith("train") for w in rows[name]["workloads"])
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           name + ".py"))


def test_toy_serve_run_reports_the_span_metrics():
    """The readers against the program's real ring: a toy traced serve run
    on the CPU with the new metrics listed for the toy cell. The idle
    shares need device operations, which the CPU's trace has none of."""
    manifest = harness.load_json(os.path.join(BASE, "BENCHMARK.json"))
    listed = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    manifest["per_layer"] += [dict(listed[n], workloads=["tiny-serve"])
                              for n in NEW]
    result = run.run_cell("tiny-serve", 2**31 + 5, 2.0, True,
                          manifest=manifest, base=BASE,
                          t_start=time.perf_counter())
    line = json.loads(json.dumps(result))
    assert line["correct"] is True
    got = line["metrics"]
    for name in NEW[:2] + NEW[3:4]:
        assert got[name]["unit"] == "ms" and got[name]["value"] > 0, name
    assert got["tick_host_p50_ms.serve"]["value"] < \
        got["decode_tick_p50_ms.serve"]["value"]
    assert not set(NEW[4:]) & set(got)
