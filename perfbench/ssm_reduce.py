"""The recurrent state update of the traced tail, from two sources laid side
by side: the decoder's ticks of the tail (`ctx["traced"]["ticks"]`, the
program's `serve.batch` spans with `ssm_lanes` and `ssm_state_bytes`, set on
the host before each dispatch) and the device's events that produce the
state pool's `ssm` leaf. The trace names a device event by its HLO
instruction, which carries the dtype and shape of what it produces: the
update is found as the events that produce `f32[lanes,H,P,N]`, whatever
implements it (a fusion today, which reads S and writes S and y; a kernel of
a stable name would produce the same shape). An event that only read the
state would name another shape and go uncounted; the tick has none. An
admission's write of one lane's state into the pool produces that shape too
and is counted with it. Bytes and seconds are of the same leaf: the
program's `ssm_state_bytes` counts the `ssm` leaf alone, and the conv
tail's events (a hundredth of the bytes, another shape) are in neither.

Nothing to read, no number: a program without the attributes (or a model
without recurrent state) gives None, never an error.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def state_events(ctx: Dict[str, Any]):
    """Device events of chip 0 that produce the state pool's shape."""
    lanes = None
    for report in (ctx.get("kv") or {}).values():
        if report.get("state_lanes"):
            lanes = int(report["state_lanes"])
    if not lanes:
        return []
    conf = ctx["conf"]
    shape = (f"f32[{lanes},{conf['mamba_n_heads']},{conf['mamba_d_head']},"
             f"{conf['mamba_d_state']}]")
    return [ev for ev in ctx["trace"].device_ops.get(0, [])
            if shape in ev[2]]


def tail(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Over the traced tail: its ticks, the least bytes their state updates
    move (the program's own count), and the device seconds of the events
    that moved them."""
    ticks = (ctx.get("traced") or {}).get("ticks") or []
    if not ticks or any("ssm_state_bytes" not in t["attrs"] for t in ticks):
        return None
    events = state_events(ctx)
    if not events:
        return None
    return {"ticks": float(len(ticks)),
            "bytes": float(sum(t["attrs"]["ssm_state_bytes"]
                               for t in ticks)),
            "seconds": float(sum(e - s for s, e, _ in events))}
