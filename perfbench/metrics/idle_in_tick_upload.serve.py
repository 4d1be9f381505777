"""Share of the traced tail in which no operation ran on the device, before
the gap's dispatch point, and the decoder's worker was building and
sending the tick's inputs: `serve.tick.upload`."""
from perfbench import host_gap


def read(ctx):
    return host_gap.idle_percent(ctx, "upload")
