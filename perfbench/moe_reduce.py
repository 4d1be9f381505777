"""The decode tick's expert products in the traced tail, from two sources
laid side by side: the decoder's ticks of the tail (`ctx["traced"]["ticks"]`,
the program's `serve.batch` spans with `moe_rows`, set before the dispatch,
and `moe_experts_hit`, the tick's own count of the (layer, expert) pairs that
got a live lane's row) and the device's events that read an expert layer's
matrices for the lanes' rows.

The profile names a device event by its HLO instruction WITHOUT its
metadata, so the scope `tick.moe_experts` does not reach the name (seen on
the chip, PERF.md section 6, PR 37); what the name carries is the dtype and
shape of the result and of every operand. The tick's expert products are
found as the events that read a buffer of an expert matrix's shape
(`[E, d, 2f]` gate | up, or `[E, f, d]` down) beside activations of the
lanes' rows (`lanes` rows where every expert meets every lane, `lanes *
top_k` where the rows are grouped), whatever implements them. An admission's
products read the same matrices for another count of rows and are not
counted.

Nothing to read, no number: a program without the attributes (or a model
without experts) gives None, never an error.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional


def _lanes(ctx: Dict[str, Any]) -> Optional[int]:
    for report in (ctx.get("kv") or {}).values():
        if report.get("lanes"):
            return int(report["lanes"])
    return None


def expert_events(ctx: Dict[str, Any]) -> List[Any]:
    """Device events of chip 0 that read an expert matrix for the tick's
    rows."""
    conf, lanes = ctx["conf"], _lanes(ctx)
    need = ("moe_num_primary_experts", "hidden_size", "moe_ffn_hidden_size",
            "moe_num_active_primary_experts")
    if not lanes or any(k not in conf for k in need):
        return []
    e, d, f, k = (conf[key] for key in need)
    matrices = (f"[{e},{d},{2 * f}]", f"[{e},{f},{d}]")
    rows = (f"[{lanes},{d}]", f"[{e},{lanes},{2 * f}]", f"[{e},{lanes},{f}]",
            f"[{lanes * k},{d}]", f"[{lanes * k},{2 * f}]",
            f"[{lanes * k},{f}]")
    return [ev for ev in ctx["trace"].device_ops.get(0, [])
            if any(m in ev[2] for m in matrices)
            and any(r in ev[2] for r in rows)]


def tail(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Over the traced tail: its ticks, the (layer, expert) pairs their live
    lanes reached, and the device seconds of the tick's expert products."""
    ticks = (ctx.get("traced") or {}).get("ticks") or []
    if not ticks or any("moe_experts_hit" not in t["attrs"] for t in ticks):
        return None
    events = expert_events(ctx)
    if not events:
        return None
    return {"ticks": float(len(ticks)),
            "hit": float(sum(t["attrs"]["moe_experts_hit"] for t in ticks)),
            "seconds": float(sum(e - s for s, e, _ in events))}
