"""The serving window's share of the chip's bf16 peak for the routed-expert
model: for every prompt token prefilled and every output token decoded in the
window, 2 x the parameters a token USES (attention, router, its six experts,
head: perfbench/flops_smallthinker.py), plus attention over the pairs
attended, a window layer's capped at its window, over the window and the
peak. The tokens are the clients' (host clock). The pairs need each query's
own context, which the window's totals do not keep: the decoded tokens' come
from the window's ticks (`kv_live`, the positions the live lanes see, a mean
over the KV layers), the prefills' from the window's admissions
(`prompt_tokens`); without those spans, no number. Small by nature in decode,
where bytes and not operations bound the tick; it is the share of the whole
step that still bounds a claim once a kernel is swapped."""
from perfbench import flops_smallthinker as fl
from perfbench import span_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    ticks = ctx.get("spans") or []
    admits = span_reduce.window_spans(ctx, "serve.admit")
    if not ticks or admits is None or \
            any("kv_live" not in s["attrs"] for s in ticks):
        return None
    conf, w = ctx["conf"], ctx["window"]
    layers = fl.layer_kinds(conf)
    pairs = (layers["global"] + layers["window"]) \
        * float(sum(s["attrs"]["kv_live"] for s in ticks))
    pairs += sum(fl.prefill_layer_pairs(conf, s["attrs"]["prompt_tokens"])
                 for s in admits if "prompt_tokens" in s["attrs"])
    work = fl.forward_flops(conf, w["prefill_tokens"] + w["decode_tokens"],
                            pairs)
    if work <= 0:
        return None
    return 100.0 * work / w["seconds"] / (ctx["cell"]["chips"]
                                          * ctx["peaks"]["bf16_flops"])
