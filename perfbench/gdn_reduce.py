"""The Gated DeltaNet state update and the held experts' products of the
traced tail, from two sources laid side by side: the decoder's ticks of the
tail (`ctx["traced"]["ticks"]`, the program's `serve.batch` spans with
`gdn_lanes` and `gdn_state_bytes`, set on the host before each dispatch, and
`moe_experts_hit`, the tick's own count) and the device's events.

The profile names a device event by its HLO instruction without its
metadata (a scope such as `tick.gdn_step` does not reach it, PERF.md section
3), and the name carries the dtype and shape of the result and of every
operand. The update is found as the events that read or write the state
pool's `f32[lanes,Hv,dk,dv]`, whatever implements them: the pass that
writes S' and any pass that only reads S (a reduction over it, S^T k)
are both the update's cost; an admission's write of one lane's state into
the pool names that shape too and is counted with it. The expert products are found as the routed-expert
cell's are (perfbench/moe_reduce.py), on the matrices of the held experts.

Nothing to read, no number: a program without the attributes (the parent)
or a model without such layers gives None, never an error.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench import moe_reduce

KEYS = ("linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim")


def state_events(ctx: Dict[str, Any]):
    """Device events of chip 0 that read or write the GDN state pool's
    shape."""
    lanes = None
    for report in (ctx.get("kv") or {}).values():
        if report.get("state_lanes"):
            lanes = int(report["state_lanes"])
    conf = ctx["conf"]
    if not lanes or any(k not in conf for k in KEYS):
        return []
    hv, dk, dv = (conf[k] for k in KEYS)
    shape = f"f32[{lanes},{hv},{dk},{dv}]"
    return [ev for ev in ctx["trace"].device_ops.get(0, [])
            if shape in ev[2]]


def tail(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Over the traced tail: its ticks, the least bytes their state updates
    move (the program's own count), and the device seconds of the events
    that moved them."""
    ticks = (ctx.get("traced") or {}).get("ticks") or []
    if not ticks or any("gdn_state_bytes" not in t["attrs"] for t in ticks):
        return None
    events = state_events(ctx)
    if not events:
        return None
    return {"ticks": float(len(ticks)),
            "bytes": float(sum(t["attrs"]["gdn_state_bytes"]
                               for t in ticks)),
            "seconds": float(sum(e - s for s, e, _ in events))}


def expert_tail(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """perfbench/moe_reduce.py's tail, on the held experts' shapes."""
    conf = ctx["conf"]
    need = ("num_experts", "hidden_size", "moe_intermediate_size",
            "num_experts_per_tok")
    if any(k not in conf for k in need):
        return None
    held = {"moe_num_primary_experts": conf["num_experts"],
            "hidden_size": conf["hidden_size"],
            "moe_ffn_hidden_size": conf["moe_intermediate_size"],
            "moe_num_active_primary_experts": conf["num_experts_per_tok"]}
    return moe_reduce.tail(dict(ctx, conf=held))
