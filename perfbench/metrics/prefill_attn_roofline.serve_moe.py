"""The admission attention kernel's share of its roofline over the traced
tail: the operations of the (query, visible key) pairs of every call seen,
one layer a call (`perfbench/flops_smallthinker.attention_flops` over
`causal_pairs` of the call's width, a window layer's capped at its window),
over the bf16 peak, over the summed device time of the calls. The least work
whatever computes it: a later kernel is read by the same yardstick.

The calls are the Mosaic events named after the kernel (`prefill_attn`, and
`prefill_attn_w<window>` for a window layer: the window comes from the
name); a call's width is the first dimension of its result, which has the
shape of its query operand, [width, heads x head_dim]. No event (the parent,
where the admission attends in plain XLA, or a run that traced no
admission), no number.
"""
import re

from perfbench import flops_smallthinker as fl

MOSAIC = 'custom_call_target="tpu_custom_call"'
CALL = re.compile(r"^\s*(?:ROOT )?%prefill_attn(?:_w(\d+))?(?:\.\d+)? = "
                  r"\w+\[(\d+),\d+\]")


def calls(trace):
    """(width, window, seconds) of each of the kernel's events on chip 0."""
    out = []
    for start, end, name in trace.device_ops.get(0, []):
        m = CALL.match(name)
        if m and MOSAIC in name:
            out.append((int(m.group(2)), int(m.group(1) or 0), end - start))
    return out


def read(ctx):
    if ctx["peaks"] is None:
        return None
    seen = calls(ctx["trace"])
    spent = sum(s for _, _, s in seen)
    if spent <= 0:
        return None
    work = sum(fl.attention_flops(ctx["conf"], fl.causal_pairs(t, w))
               for t, w, _ in seen)
    return 100.0 * work / ctx["peaks"]["bf16_flops"] / spent
