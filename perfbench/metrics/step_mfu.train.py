"""The whole training step's share of the chip's bf16 peak: model FLOPs per
token by shape (perfbench/flops.py, recomputation not counted) times the
tokens per second of the window, over chips times peak."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    w = ctx["window"]
    per_token = ctx["flops"].train_flops_per_token(ctx["conf"],
                                                   ctx["mix"]["seq"])
    rate = w["tokens"] / w["seconds"]
    return 100.0 * per_token * rate / (ctx["cell"]["chips"]
                                       * ctx["peaks"]["bf16_flops"])
