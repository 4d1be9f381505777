"""Device milliseconds a decode tick spends on the GDN state: the summed
time of the events that read or write the shape the state S is pooled in,
in the traced tail, over the tail's ticks (perfbench/gdn_reduce.py)."""
from perfbench import gdn_reduce


def read(ctx):
    t = gdn_reduce.tail(ctx)
    if t is None:
        return None
    return 1e3 * t["seconds"] / t["ticks"]
