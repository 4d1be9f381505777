"""The serving window's share of the chip's bf16 peak for the GDN model:
for every prompt token prefilled and every output token decoded in the
window, 2 x the dense parameters it uses (GDN and attention projections,
router, shared expert, head) and one step of the delta rule; 2 x an
expert's parameters for every (row, held expert) pair, as the program counts
them (`moe_pairs_held` of the window's ticks, `admit_moe_pairs_held` of the
prefills ahead of them); attention over the pairs attended (the ticks'
`kv_live`, the admissions' `prompt_tokens`): perfbench/flops_qwen3_next.py,
over the window and the peak. The tokens are the clients' (host clock).
Without those spans, no number. Small by nature in decode, where bytes and
not operations bound the tick; it is the share of the whole step that
still bounds a claim once a kernel is swapped."""
from perfbench import flops_qwen3_next as fl
from perfbench import span_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    ticks = ctx.get("spans") or []
    admits = span_reduce.window_spans(ctx, "serve.admit")
    need = ("kv_live", "moe_pairs_held", "admit_moe_pairs_held")
    if not ticks or admits is None or \
            any(k not in s["attrs"] for s in ticks for k in need):
        return None
    conf, w = ctx["conf"], ctx["window"]
    layers = fl._dims(conf)["n_full"]
    pairs = layers * float(sum(s["attrs"]["kv_live"] for s in ticks))
    pairs += sum(fl.prefill_layer_pairs(conf, s["attrs"]["prompt_tokens"])
                 for s in admits if "prompt_tokens" in s["attrs"])
    held = float(sum(s["attrs"]["moe_pairs_held"]
                     + s["attrs"]["admit_moe_pairs_held"] for s in ticks))
    work = fl.forward_flops(conf, w["prefill_tokens"] + w["decode_tokens"],
                            held, pairs)
    if work <= 0:
        return None
    return 100.0 * work / w["seconds"] / (ctx["cell"]["chips"]
                                          * ctx["peaks"]["bf16_flops"])
