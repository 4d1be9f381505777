"""The device events of the SambaY model's three new kinds of work, from the
traced tail's events and the decoder's ticks laid side by side (as
perfbench/ssm_reduce.py and gdn_reduce.py do for theirs): the profile names
an event by its HLO instruction, without the scopes, and the name carries
the dtype and shape of the result and of every operand, or the name of the
Pallas call.

  * the tick's Mamba-1 state update: the events that read or write the
    state pool's `f32[lanes,N,inner]`;
  * the attention of the layers that read the shared keys and values: the
    tick's attention kernel (`decode_attn`) on the shared pool's buffers,
    `bf16[blocks+1,block_tokens,row]`, which no window buffer has;
  * the admission's selective scan: the Pallas call `selective_scan`.

Nothing to read, no number: a program without the attributes or the events
(the parent) gives None, never an error.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

MOSAIC = 'custom_call_target="tpu_custom_call"'
ATTEND = re.compile(r"^\s*(?:ROOT )?%decode_attn(?:\.\d+)? = ")
SCAN = re.compile(r"^\s*(?:ROOT )?%selective_scan(?:\.\d+)? = ")
KEYS = ("mamba_d_state", "hidden_size", "mamba_expand")


def _events(ctx: Dict[str, Any]) -> List[Tuple[float, float, str]]:
    return ctx["trace"].device_ops.get(0, [])


def _seconds(events) -> float:
    return float(sum(e - s for s, e, _ in events))


def _ticks(ctx: Dict[str, Any], key: str):
    ticks = (ctx.get("traced") or {}).get("ticks") or []
    if not ticks or any(key not in t["attrs"] for t in ticks):
        return None
    return ticks


def state_tail(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The traced ticks, their Mamba-1 state bytes (the program's own
    count) and the device seconds of the events on the pool's shape."""
    ticks = _ticks(ctx, "selective_state_bytes")
    conf = ctx["conf"]
    lanes = max((int(r.get("state_lanes") or 0)
                 for r in (ctx.get("kv") or {}).values()), default=0)
    if ticks is None or not lanes or any(k not in conf for k in KEYS):
        return None
    shape = (f"f32[{lanes},{conf['mamba_d_state']},"
             f"{conf['mamba_expand'] * conf['hidden_size']}]")
    events = [ev for ev in _events(ctx) if shape in ev[2]]
    if not events:
        return None
    return {"ticks": float(len(ticks)),
            "bytes": float(sum(t["attrs"]["selective_state_bytes"]
                               for t in ticks)),
            "seconds": _seconds(events)}


def shared_tail(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The traced ticks' shared positions read (``shared_kv_live``: live
    positions of the shared pool x the layers that read it) and the device
    seconds of the attention kernel's events on the shared pool's buffers."""
    ticks = _ticks(ctx, "shared_kv_live")
    shared = [(r, g) for r in (ctx.get("kv") or {}).values()
              for g in r.get("groups") or () if not g.get("window")]
    if ticks is None or len(shared) != 1:
        return None
    report, group = shared[0]
    bt = report.get("block_tokens")
    if not bt:
        return None
    buffer = f"bf16[{group['blocks'] + 1},{bt},"
    events = [ev for ev in _events(ctx) if ATTEND.match(ev[2])
              and MOSAIC in ev[2] and buffer in ev[2]]
    if not events:
        return None
    return {"positions": float(sum(t["attrs"]["shared_kv_live"]
                                   for t in ticks)),
            "seconds": _seconds(events)}


def scan_tail(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The bucket widths the traced tail's admissions scanned (the ticks'
    ``admit_width_sum``) and the device seconds of the scan's events."""
    ticks = _ticks(ctx, "admit_width_sum")
    if ticks is None:
        return None
    events = [ev for ev in _events(ctx) if SCAN.match(ev[2])
              and MOSAIC in ev[2]]
    width = sum(t["attrs"]["admit_width_sum"] for t in ticks)
    if not events or not width:
        return None
    return {"width": float(width), "seconds": _seconds(events)}
