"""The serving window's share of the chip's bf16 peak for the SambaY model:
for every output token decoded in the window 2 x the parameters the tick
multiplies (all layers and the tied head) and a step of the scan; for every
prompt token prefilled 2 x what the admission multiplies (the self-decoder
and the full layer's K and V projection: it stops there) and a step of the
scan; attention over the pairs attended (the ticks' window pairs, capped at
the window, and the shared pool's pairs times the layers that read it, from
`kv_live` and `shared_kv_live`; the admissions' window pairs from
`prompt_tokens`): perfbench/flops_phi4flash.py, over the window and the
peak. The tokens are the clients' (host clock). Without those spans, no
number."""
from perfbench import flops_phi4flash as fl
from perfbench import span_reduce
from perfbench.reference_phi4flash import dims


def read(ctx):
    if ctx["peaks"] is None:
        return None
    ticks = ctx.get("spans") or []
    admits = span_reduce.window_spans(ctx, "serve.admit")
    if not ticks or admits is None or any(
            k not in s["attrs"] for s in ticks
            for k in ("kv_live", "shared_kv_live")):
        return None
    conf, w = ctx["conf"], ctx["window"]
    # kv_live is the reads' positions over the KV layers: times the KV
    # layers, the sum of every reading layer's pairs
    pairs = float(sum(dims(conf)["n_attn"] * s["attrs"]["kv_live"]
                      for s in ticks))
    pairs += sum(fl.admit_layer_pairs(conf, s["attrs"]["prompt_tokens"])
                 for s in admits if "prompt_tokens" in s["attrs"])
    work = fl.serve_flops(conf, w["decode_tokens"], w["prefill_tokens"],
                          pairs)
    if work <= 0:
        return None
    return 100.0 * work / w["seconds"] / (ctx["cell"]["chips"]
                                          * ctx["peaks"]["bf16_flops"])
