"""The serve tick's host gap split by cause: each idle gap of the device in
the traced tail, laid over the decoder worker's own spans on the host plane,
put down to what the worker was doing before the program that ended the gap
was handed to the device, and the rest of the gap to the program already
being on the device's queue.

The profile puts the device's operations on the host's clock only to
within a fraction of a millisecond to 1.3 ms, and the error differs from
one profile to the next (TPU v5e, libtpu 0.0.34). `clock_offset`
measures it in each trace: the TPU runtime's host event DoEnqueueProgram
puts a program on the device's queue, and a device that waited for that
program starts it as the event ends. The median over the gaps of 0.1 ms or
more of (the nearest such end less the gap's end) is what is added to the
device's times before they are laid over the spans; a trace without the
event is laid over as it stands.

A dispatch call (`serve.tick.dispatch`, or an admission's
`serve.admit.dispatch`) hands its program over somewhere inside it, and the
device may start the program before the call returns: the call still wraps
its outputs. So a gap's dispatch point is the end of the first call that
overlaps it, or the gap's own end where that call is still open there. For
each gap between two device operations of chip 0:

- where no dispatch call overlaps it, the whole gap is AFTER_DISPATCH: the
  program that ended it was on the device's queue before the device ran
  dry (a gap between two operations of one program among them), and
  dispatching earlier cannot shorten it;
- else the gap from its dispatch point on is AFTER_DISPATCH (nothing where
  the device started the program inside the call), and the part before
  goes to the innermost of the program's spans open there, through CLASS,
  or to `none` where none is.

What the traced tail holds before its first operation and after its last
(the tail is timed by the host's clock) is `none`, as in
`span_reduce.idle_by_phase`, so the classes add up to `device_idle.serve`
of the same run. A program without `serve.tick.dispatch` (the parent of
the change that brought it) gives None: nothing to split by.

The arithmetic (`split`, `clock_offset`) is plain Python over intervals
and is tested on hand-built ones
(tests/test_perfbench_host_gap.py).
"""
from __future__ import annotations

import bisect
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from perfbench import span_reduce, trace_reduce

Interval = Tuple[float, float]
Labelled = Tuple[float, float, str]       # start, end, label

AFTER_DISPATCH = "after_dispatch"
NONE = span_reduce.UNATTRIBUTED
DISPATCH_CALLS = ("serve.tick.dispatch", "serve.admit.dispatch")
ENQUEUE = "DoEnqueueProgram"
# a gap the host can have caused: gaps between two operations of one
# program last a few microseconds
HOST_GAP_S = 1e-4

# the class of each span of the decoder's worker; a span's own part (what
# its children leave) is its class, and the innermost span wins
CLASS = {
    "serve.tick.upload": "upload",
    "serve.tick.dispatch": "dispatch",
    "serve.tick.wait": "readback", "serve.tick.read_keys": "readback",
    "serve.sweep": "booking", "serve.tick.plan": "booking",
    "serve.batch": "booking", "serve.tick.stage": "booking",
    "serve.tick.emit": "booking", "serve.tick.deliver": "booking",
    "serve.admit": "admit", "serve.admit.dispatch": "admit",
    "serve.idle": "idle", "serve.gather": "idle",
}
CLASSES = (AFTER_DISPATCH, "upload", "dispatch", "readback", "booking",
           "admit", "idle", NONE)


def split(idle: Sequence[Interval], host: Sequence[Labelled]
          ) -> Optional[Dict[str, float]]:
    """Seconds of the idle gaps `idle` (disjoint, sorted) by class, from
    the host events `host` (start, end, name; names outside CLASS are
    left out). None where the host holds no `serve.tick.dispatch`."""
    spans = [(s, e, name) for s, e, name in host if name in CLASS]
    if not any(name == DISPATCH_CALLS[0] for _, _, name in spans):
        return None
    # one thread's calls: disjoint, so sorted by start they are by end too
    calls = sorted((s, e) for s, e, name in spans if name in DISPATCH_CALLS)
    ends = [e for _, e in calls]
    before: List[Interval] = []
    after = 0.0
    for s, e in idle:
        k = bisect.bisect_right(ends, s)      # the first call to end after s
        point = min(ends[k], e) if k < len(calls) and calls[k][0] < e else s
        if point > s:
            before.append((s, point))
        after += e - point
    segments = span_reduce.label_segments((s, e, CLASS[name])
                                          for s, e, name in spans)
    out = dict.fromkeys(CLASSES, 0.0)
    out.update(span_reduce.split_gaps(before, segments))
    out[AFTER_DISPATCH] = after
    return out


def clock_offset(idle: Sequence[Interval], host: Sequence[Labelled]) -> float:
    """Seconds to add to a time of the device's to read it on the host's
    clock: the median over the gaps `idle` of HOST_GAP_S or more of the
    end of the nearest ENQUEUE event less the gap's end; 0 where there is
    no such gap or event."""
    enqueued = sorted(e for _, e, name in host if name == ENQUEUE)
    diffs = []
    for s, e in idle:
        if e - s < HOST_GAP_S or not enqueued:
            continue
        k = bisect.bisect_left(enqueued, e)
        near = min((enqueued[i] for i in (k - 1, k) if 0 <= i < len(enqueued)),
                   key=lambda t: abs(t - e))
        diffs.append(near - e)
    return statistics.median(diffs) if diffs else 0.0


def idle_by_class(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Percent of the traced tail in which no operation ran on the device,
    by class; they add up to what `device_idle.serve` reads of the same
    run. None where no operation was traced or the program has no
    dispatch span."""
    trace = ctx["trace"]
    ops = trace.device_ops.get(0, [])
    if not ops:
        return None
    idle = trace_reduce.gaps((s, e) for s, e, _ in ops)
    shift = clock_offset(idle, trace.host)
    seconds = split([(s + shift, e + shift) for s, e in idle], trace.host)
    if seconds is None:
        return None
    window_s = ctx["traced"]["window_s"]
    seconds[NONE] += (window_s - trace.busy_s()) - sum(e - s for s, e in idle)
    return {label: 100.0 * v / window_s for label, v in seconds.items()}


def idle_percent(ctx: Dict[str, Any], label: str) -> Optional[float]:
    shares = idle_by_class(ctx)
    return None if shares is None else shares[label]
