"""Device milliseconds a decode tick spends on the recurrent state update:
the summed time of the events that produce the shape the state S is pooled
in, in the traced tail, over the tail's ticks (perfbench/ssm_reduce.py)."""
from perfbench import ssm_reduce


def read(ctx):
    t = ssm_reduce.tail(ctx)
    if t is None:
        return None
    return 1e3 * t["seconds"] / t["ticks"]
