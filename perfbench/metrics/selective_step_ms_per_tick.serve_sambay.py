"""Device milliseconds a traced tick spends computing on the Mamba-1 state:
the events that read or write the pool's `f32[lanes,N,inner]`
(perfbench/sambay_reduce.py) over the tail's ticks. No share of a roofline:
a layer's state (21 MB at 64 lanes) fits the chip's VMEM, and the compiler
moves it there and back by asynchronous copies that run under other
operations and are no event of the trace's op line, so the events' time is
the update's computing on the state in VMEM, and bytes over the HBM peak
over it would read above 100%."""
from perfbench import sambay_reduce


def read(ctx):
    t = sambay_reduce.state_tail(ctx)
    if t is None or t["ticks"] <= 0:
        return None
    return 1000.0 * t["seconds"] / t["ticks"]
