"""The decode tick's held expert products' share of their roofline over the
traced ticks: the least time the chip could take to read, once each, the
three matrices of every (layer, held expert) pair that a live lane's row
reached (the program's `moe_experts_hit` a tick x 3 x d x f x 2 bytes, over
the HBM peak), over the device time of the events that read the held
experts' matrices for the lanes' rows (perfbench/gdn_reduce.py). A form
that reads every held expert whether a row reached it or not pays for the
idle ones in time and gets no bytes for them."""
from perfbench import flops_qwen3_next, gdn_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    t = gdn_reduce.expert_tail(ctx)
    if t is None or t["seconds"] <= 0:
        return None
    byts = t["hit"] * flops_qwen3_next.expert_bytes(ctx["conf"])
    return 100.0 * (byts / ctx["peaks"]["hbm_bytes_per_s"]) / t["seconds"]
