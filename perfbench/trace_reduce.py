"""From a profiler trace to numbers: the device's busy time, the operations
that took most of it, the longest idle gaps and what the host was doing in
them, and a kernel's summed time. `jax.profiler.ProfileData` reads the
`.xplane.pb`; nothing else is needed.

Intervals are (start, end) in seconds on one clock. The arithmetic
(`busy_union`, `gaps`, `self_times`) is plain Python over such intervals and
is tested on hand-built ones (perfbench/tests/test_trace_reduce.py).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[float, float, str]          # start, end, name

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_union(intervals: Iterable[Interval]) -> float:
    """Seconds in which at least one interval is open."""
    return sum(e - s for s, e in merge(intervals))


def gaps(intervals: Iterable[Interval], window: Optional[Interval] = None
         ) -> List[Interval]:
    """The idle stretches between busy intervals, and from the window's
    edges where a window is given."""
    merged = merge(intervals)
    out: List[Interval] = []
    if window is not None:
        merged = [(max(s, window[0]), min(e, window[1])) for s, e in merged
                  if e > window[0] and s < window[1]]
        edge = window[0]
    elif merged:
        edge = merged[0][0]
    else:
        return out
    for s, e in merged:
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if window is not None and window[1] > edge:
        out.append((edge, window[1]))
    return out


def idle_share(intervals: Iterable[Interval], window: Interval) -> float:
    length = window[1] - window[0]
    clipped = [(max(s, window[0]), min(e, window[1])) for s, e in intervals]
    return 1.0 - busy_union(clipped) / length


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds by name, each event less the part its nested events cover
    (a loop's event encloses its body's), so that the names add up to the
    busy time of a line whose events nest but do not otherwise overlap."""
    total: Dict[str, float] = {}
    stack: List[List] = []        # [end, name, self seconds]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _, name, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -(ev[1]))):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return total


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def innermost(host_events: Sequence[Event], at: float) -> str:
    """The shortest host event open at time `at`: what the host was doing."""
    best, best_len = "host idle or untraced", float("inf")
    for s, e, name in host_events:
        if s <= at <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _events(line) -> List[Event]:
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name) for ev in line.events]


HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\])?.*? ([a-z][\w\-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """A device event is named by its whole HLO line; keep the result's
    name, the opcode, the (first) result's type and, of a custom call, its
    target: `%closed_call.7 custom-call bf16[12,2048,128] tpu_custom_call`."""
    m = HLO.match(name)
    if not m:
        return name[:120]
    target = TARGET.search(name) if m.group(3) == "custom-call" else None
    return " ".join(x for x in (m.group(1), m.group(3), m.group(2),
                                target and target.group(1)) if x)


class Trace:
    """The device operations of each chip and the host's events, from one
    `.xplane.pb`."""

    def __init__(self, logdir: str, chips: int = 1) -> None:
        from jax.profiler import ProfileData

        self.path = find_xplane(logdir)
        data = ProfileData.from_file(self.path)
        self.device_ops: Dict[int, List[Event]] = {}
        self.host: List[Event] = []
        self.lines_seen: List[str] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            lines = list(plane.lines)
            self.lines_seen += [f"{plane.name} | {ln.name}" for ln in lines]
            if m and int(m.group(1)) < chips:
                ops = [ln for ln in lines if ln.name == OPS_LINE]
                if ops:
                    self.device_ops[int(m.group(1))] = _events(ops[0])
            elif plane.name.startswith("/host:CPU"):
                for ln in lines:
                    self.host += _events(ln)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.device_ops:
            return 0.0
        return sum(busy_union((s, e) for s, e, _ in ops)
                   for ops in self.device_ops.values()) / len(self.device_ops)

    def idle_percent(self, window_s: float) -> Optional[float]:
        """Share of `window_s`, the traced stretch by the host's clock, in
        which no operation ran; nothing where no operation was traced."""
        busy = self.busy_s()
        return 100.0 * (1.0 - busy / window_s) if busy > 0 else None

    def op_seconds(self) -> Dict[str, float]:
        """Self time by operation name, averaged over the chips."""
        total: Dict[str, float] = {}
        for ops in self.device_ops.values():
            for k, v in self_times(ops).items():
                k = short_name(k)
                total[k] = total.get(k, 0.0) + v / len(self.device_ops)
        return total

    def breakdown(self) -> Dict[str, List[List]]:
        ops0 = self.device_ops.get(0, [])
        idle = gaps((s, e) for s, e, _ in ops0)
        idle.sort(key=lambda g: g[0] - g[1])
        named: Dict[str, float] = {}
        for s, e in idle[:200]:
            what = innermost(self.host, (s + e) / 2)
            named[what] = max(named.get(what, 0.0), e - s)
        return {"device_ops": top(self.op_seconds()),
                "idle_gaps": top(named)}


def start(logdir: str) -> None:
    """Start the profiler with the host's Python tracer off: it is what
    makes a trace large and slows the host that feeds the chip."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
    except (AttributeError, TypeError):
        jax.profiler.start_trace(logdir)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()
