"""Share of the traced tail in which no operation ran on the device, before
the gap's dispatch point, and the decoder's worker was reading a
tick's outputs back: `serve.tick.wait` (the tokens) or
`serve.tick.read_keys` (the sampling keys)."""
from perfbench import host_gap


def read(ctx):
    return host_gap.idle_percent(ctx, "readback")
