"""The decode tick's attention kernel's share of its roofline over the traced
tail: the least time the chip could take to read, once, the K and V that the
traced ticks' live lanes can see (the program's `kv_live` a tick: positions
summed over the live lanes, averaged over the KV layers, a window layer's
capped at its window; times the K and V bytes a position of every KV layer,
the decoder's `block_bytes` over `block_tokens` in `kv`) at the HBM peak,
over the summed device time of the kernel's events. The least work whatever
reads it: a later kernel is read by the same yardstick.

The events are the Mosaic calls named after the kernel (`decode_attn`,
ops/pallas_paged.name). Ticks that ran the gather (`attend` other than
`kernel`) give no number, nor does a run with no such event: the parent,
whose tick gathers in plain XLA.
"""
import re

MOSAIC = 'custom_call_target="tpu_custom_call"'
CALL = re.compile(r"^\s*(?:ROOT )?%decode_attn(?:\.\d+)? = ")


def seconds(trace):
    """Device seconds of the kernel's events on chip 0."""
    return sum(end - start for start, end, name
               in trace.device_ops.get(0, [])
               if CALL.match(name) and MOSAIC in name)


def read(ctx):
    ticks = (ctx.get("traced") or {}).get("ticks") or []
    reports = [r for r in (ctx.get("kv") or {}).values()
               if r.get("block_bytes") and r.get("block_tokens")]
    if ctx.get("peaks") is None or not ticks or len(reports) != 1 or any(
            t["attrs"].get("attend") != "kernel" or "kv_live" not in t["attrs"]
            for t in ticks):
        return None
    spent = seconds(ctx["trace"])
    if spent <= 0:
        return None
    per_position = reports[0]["block_bytes"] / reports[0]["block_tokens"]
    live = sum(t["attrs"]["kv_live"] for t in ticks) * per_position
    return 100.0 * live / ctx["peaks"]["hbm_bytes_per_s"] / spent
