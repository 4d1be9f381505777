"""The serving window's share of the chip's bf16 peak: 2 x matmul parameters
for every prompt token prefilled and every output token decoded in the
window, plus attention over the pairs each attended, by shape
(perfbench/flops.py), over the window and the peak. Small by nature in
decode; it is what still bounds a claim once a kernel is swapped."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    w = ctx["window"]
    work = ctx["flops"].forward_flops(
        ctx["conf"], w["prefill_tokens"] + w["decode_tokens"],
        w["attended_pairs"])
    if work <= 0:
        return None
    return 100.0 * work / w["seconds"] / (ctx["cell"]["chips"]
                                          * ctx["peaks"]["bf16_flops"])
