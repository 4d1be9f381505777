"""The recurrent state update's share of its roofline over the traced
ticks: the least time the chip could take to read each live lane's state S
once and write it once (the program's `ssm_state_bytes` a tick, over the HBM
peak: the update is bound by bytes, 5 operations to 8 bytes) over the device
time of the events that produce the shape S is pooled in
(perfbench/ssm_reduce.py). Dead lanes that the program advances besides, and
anything it copies, cost time and no bytes here: that is the point."""
from perfbench import ssm_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    t = ssm_reduce.tail(ctx)
    if t is None or t["seconds"] <= 0:
        return None
    return 100.0 * (t["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]) \
        / t["seconds"]
