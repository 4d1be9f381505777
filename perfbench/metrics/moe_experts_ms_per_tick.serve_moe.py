"""Device milliseconds a decode tick spends in its expert products: the summed
time of the events that read the expert matrices for the lanes' rows, in the
traced tail, over the tail's ticks (perfbench/moe_reduce.py)."""
from perfbench import moe_reduce


def read(ctx):
    t = moe_reduce.tail(ctx)
    if t is None:
        return None
    return 1e3 * t["seconds"] / t["ticks"]
