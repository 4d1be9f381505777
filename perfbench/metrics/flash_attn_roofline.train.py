"""The flash-attention forward kernel's share of its roofline in the traced
training steps: the least time the chip could take for the calls seen
(causal operations and least bytes by shape, perfbench/flops.py) over the
summed device time of the kernel's events.

Only the forward pass is a Mosaic kernel in this program; its backward is
plain XLA inside a scan and is not told apart in the trace. The events are
found by what the trace gives today, a custom call with the Mosaic
target on operands of the attention's shape (see PERF.md, Open questions:
stable names). No event, no number.
"""
MOSAIC = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx["peaks"] is None:
        return None
    conf, mix = ctx["conf"], ctx["mix"]
    accum = ctx["cell"].get("program", {}).get("accum_steps", 1)
    rows = (mix["batch"] // accum) * conf["n_head"]
    # a Mosaic custom call whose operands have the attention's shape
    shape = f"[{rows},{mix['seq']},{conf['n_embd'] // conf['n_head']}]"
    events = [ev for ev in ctx["trace"].device_ops.get(0, [])
              if " custom-call(" in ev[2] and MOSAIC in ev[2]
              and shape in ev[2]]
    if not events:
        return None
    call = ctx["flops"].flash_forward_call(
        rows, mix["seq"], conf["n_embd"] // conf["n_head"])
    least, _bound = ctx["flops"].least_seconds(call["flops"], call["bytes"],
                                               ctx["peaks"])
    spent = sum(e - s for s, e, _ in events)
    return 100.0 * least * len(events) / spent
