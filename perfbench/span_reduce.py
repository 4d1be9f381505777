"""From the program's own spans to numbers: the serving window's queue wait,
admission and tick-phase times out of the tracer's ring, and the traced
tail's idle time put down to the scheduler phase the worker was in.

Two sources. The ring (`deeplearning4j_tpu.obs.trace.tracer().spans(name)`)
holds every span since the job cleared it at the window's start, on the
host's monotonic clock; the window is read up to the last tick of
`ctx["spans"]`. The traced tail's host plane (`ctx["trace"].host`)
holds the same spans as profiler annotations, on the clock of the device's
operations, so an idle gap of the device is laid over them directly and no
clock is converted.

A program without these spans (the parent of the PR that brought them) makes
every function here return None: nothing to read, no number. The arithmetic
(`label_segments`, `split_gaps`) is plain Python over intervals and is tested
on hand-built ones (perfbench/tests/test_span_readers.py).
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import trace_reduce

Interval = Tuple[float, float]
Labelled = Tuple[float, float, str]       # start, end, label

# the scheduler phase each annotated span of the decoder's worker belongs
# to; a child and its parent share a phase, so nesting moves nothing
# between phases
PHASE = {
    "serve.admit": "admit", "serve.admit.dispatch": "admit",
    "serve.sweep": "tick", "serve.tick.plan": "tick",
    "serve.batch": "tick", "serve.tick.stage": "tick",
    "serve.tick.wait": "tick", "serve.tick.emit": "tick",
    "serve.idle": "idle",
}
UNATTRIBUTED = "none"


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------


def window(ctx: Dict[str, Any]) -> Optional[float]:
    """The start of the window's last tick, up to which the ring is read,
    or None where the ring cannot be trusted to hold every span up to it:
    the window has no tick, or the ring dropped spans since the job cleared
    it (a median over a truncated window is worse than none), or the tracer
    keeps no such count (a program from before these spans). The lower edge
    is the window's own (`ctx["t0"]`, where the job clears the ring): a queue
    wait or an admission that starts before the window's first tick belongs
    to it, one that started in the warm-in does not."""
    from deeplearning4j_tpu.obs import trace as obs_trace

    ticks = ctx.get("spans") or []
    if not ticks or getattr(obs_trace.tracer(), "dropped", None) != 0:
        return None
    return max(s["t_mono"] for s in ticks)


def window_spans(ctx: Dict[str, Any], name: str
                 ) -> Optional[List[Dict[str, Any]]]:
    """The finished spans `name` that started inside the window."""
    from deeplearning4j_tpu.obs import trace as obs_trace

    until = window(ctx)
    if until is None:
        return None
    since = ctx.get("t0", float("-inf"))
    return [s for s in obs_trace.tracer().spans(name)
            if s["duration_s"] is not None and since <= s["t_mono"] <= until]


def duration_ms(ctx: Dict[str, Any], name: str,
                percentile: float) -> Optional[float]:
    """A percentile of the durations of the window's spans `name`."""
    spans = window_spans(ctx, name)
    if not spans:
        return None
    return 1e3 * float(np.percentile(
        np.asarray([s["duration_s"] for s in spans], np.float64), percentile))


def prefill_per_admit_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Tick delay per admission: what one admission adds to the tick that
    follows it, as the host sees it, and not the prefill's device time
    (what runs while the host still books the admission is not in it). Over
    the window's ticks with admissions ahead of them, the median of (the
    tick's span less the median tick with none ahead) over the admissions."""
    ticks = ctx.get("spans") or []
    if window(ctx) is None \
            or any("admits" not in s["attrs"] for s in ticks):
        return None
    clear = [s["duration_s"] for s in ticks if s["attrs"]["admits"] == 0]
    after = [s for s in ticks if s["attrs"]["admits"] >= 1]
    if not clear or not after:
        return None
    base = statistics.median(clear)
    return 1e3 * statistics.median(
        (s["duration_s"] - base) / s["attrs"]["admits"] for s in after)


def tick_host_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """The host's own share of a tick: median over the window's ticks of
    the upload-and-dispatch span inside the tick plus the unpack-and-stream
    span after it."""
    stage = window_spans(ctx, "serve.tick.stage")
    emit = window_spans(ctx, "serve.tick.emit")
    if not stage or not emit:
        return None
    staged = {s["parent_id"]: s["duration_s"] for s in stage}
    emitted = {s["attrs"].get("tick"): s["duration_s"] for s in emit}
    both = [staged[t["span_id"]] + emitted[t["span_id"]]
            for t in ctx["spans"]
            if t["span_id"] in staged and t["span_id"] in emitted]
    if not both:
        return None
    return 1e3 * statistics.median(both)


# ---------------------------------------------------------------------------
# idle gaps laid over annotated spans
# ---------------------------------------------------------------------------


def label_segments(events: Iterable[Labelled]) -> List[Labelled]:
    """Disjoint sorted stretches, each with the label of the innermost
    (shortest) event open in it; stretches no event covers are left out.
    Neighbours with one label are joined."""
    events = [ev for ev in events if ev[1] > ev[0]]
    cuts = sorted({t for s, e, _ in events for t in (s, e)})
    out: List[List] = []
    for lo, hi in zip(cuts, cuts[1:]):
        inner = min((ev for ev in events if ev[0] <= lo and ev[1] >= hi),
                    key=lambda ev: ev[1] - ev[0], default=None)
        if inner is None:
            continue
        if out and out[-1][2] == inner[2] and out[-1][1] == lo:
            out[-1][1] = hi
        else:
            out.append([lo, hi, inner[2]])
    return [(s, e, label) for s, e, label in out]


def split_gaps(idle: Sequence[Interval], segments: Sequence[Labelled]
               ) -> Dict[str, float]:
    """Seconds of the idle gaps under each label, and under UNATTRIBUTED
    what no segment covers. Both inputs are disjoint and sorted."""
    total: Dict[str, float] = {UNATTRIBUTED: 0.0}
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            lo, hi, label = segments[k]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                total[label] = total.get(label, 0.0) + part
                covered += part
            k += 1
        total[UNATTRIBUTED] += (e - s) - covered
    return total


def idle_by_phase(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Percent of the traced tail in which no operation ran on the device,
    by the scheduler phase the decoder's worker was in: `admit`, `tick`,
    `idle` (an empty pool waiting for a request) and UNATTRIBUTED. The four
    add up to what `device_idle.serve` reads of the same run: each gap
    between two device operations is split among the phases by overlap, and
    what the window holds before the first operation and after the last
    (the tail is timed by the host's clock, its edges are not on the
    trace's) goes to UNATTRIBUTED. None where no operation was traced or
    the host plane holds none of the program's spans."""
    trace = ctx["trace"]
    ops = trace.device_ops.get(0, [])
    spans = [(s, e, PHASE[name]) for s, e, name in trace.host
             if name in PHASE]
    if not ops or not spans:
        return None
    window_s = ctx["traced"]["window_s"]
    idle = trace_reduce.gaps((s, e) for s, e, _ in ops)
    split = split_gaps(idle, label_segments(spans))
    edges = (window_s - trace.busy_s()) - sum(e - s for s, e in idle)
    split[UNATTRIBUTED] += edges
    return {label: 100.0 * seconds / window_s
            for label, seconds in split.items()}


def idle_percent(ctx: Dict[str, Any], phase: str) -> Optional[float]:
    split = idle_by_phase(ctx)
    return None if split is None else split.get(phase, 0.0)
