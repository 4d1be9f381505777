"""The serving window's share of the chip's bf16 peak for the hybrid model:
for every prompt token prefilled and every output token decoded in the
window, 2 x matmul parameters and one step of the recurrence, plus the
attention layers' scores and values over the pairs each attended, by shape
(perfbench/flops_granite.py), over the window and the peak. Small by nature
in decode, where bytes and not operations bound the tick; it is the share of
the whole step that still bounds a claim once a kernel is swapped."""
from perfbench import flops_granite


def read(ctx):
    if ctx["peaks"] is None:
        return None
    w = ctx["window"]
    work = flops_granite.forward_flops(
        ctx["conf"], w["prefill_tokens"] + w["decode_tokens"],
        w["attended_pairs"])
    if work <= 0:
        return None
    return 100.0 * work / w["seconds"] / (ctx["cell"]["chips"]
                                          * ctx["peaks"]["bf16_flops"])
