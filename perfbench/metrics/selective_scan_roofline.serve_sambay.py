"""The admission's selective scan's share of its roofline over the traced
tail: the least time the chip could take to read the scan's inputs (delta,
delta x, B, C) and write its output y once, float32, over the Mamba-1
layers, at the bucket widths the tail's admissions ran
(perfbench/flops_phi4flash.scan_bytes), at the HBM peak, over the device
time of the Pallas calls `selective_scan` (perfbench/sambay_reduce.py).
The scan is sequential in time: this says how far its walk stands from the
bytes it must move."""
from perfbench import flops_phi4flash as fl
from perfbench import sambay_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    t = sambay_reduce.scan_tail(ctx)
    if t is None or t["seconds"] <= 0:
        return None
    moved = fl.scan_bytes(ctx["conf"], int(t["width"]))
    return 100.0 * (moved / ctx["peaks"]["hbm_bytes_per_s"]) / t["seconds"]
