"""The share of its roofline of the attention that reads the one shared KV
buffer (the full attention layer and the cross-decoder's layers): the least
time the chip could take to read, once for each layer that reads them, the
shared keys and values the traced ticks' live lanes can see (the program's
`shared_kv_live` x the K and V bytes of a position in one buffer,
perfbench/flops_phi4flash.kv_bytes_per_token) at the HBM peak, over the
device time of the attention kernel's events on the shared pool's buffers
(perfbench/sambay_reduce.py)."""
from perfbench import flops_phi4flash as fl
from perfbench import sambay_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    t = sambay_reduce.shared_tail(ctx)
    if t is None or t["seconds"] <= 0:
        return None
    moved = t["positions"] * fl.kv_bytes_per_token(ctx["conf"])
    return 100.0 * (moved / ctx["peaks"]["hbm_bytes_per_s"]) / t["seconds"]
