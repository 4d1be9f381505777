"""The decode tick's expert products' share of their roofline over the traced
ticks: the least time the chip could take to read, once each, the three
matrices of every (layer, expert) pair that a live lane's row reached (the
program's `moe_experts_hit` a tick x 3 x d x f x 2 bytes, over the HBM peak:
at 6 rows a lane the products are bound by the weights' bytes), over the
device time of the events that read the expert matrices for the lanes' rows
(perfbench/moe_reduce.py). The least bytes the work needs whatever computes
it: a form that reads every held expert pays for the idle ones in time and
gets no bytes for them, one that skips them is read by the same yardstick."""
from perfbench import flops_smallthinker, moe_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    t = moe_reduce.tail(ctx)
    if t is None or t["seconds"] <= 0:
        return None
    byts = t["hit"] * flops_smallthinker.expert_bytes(ctx["conf"])
    return 100.0 * (byts / ctx["peaks"]["hbm_bytes_per_s"]) / t["seconds"]
