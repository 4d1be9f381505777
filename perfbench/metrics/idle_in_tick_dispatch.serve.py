"""Share of the traced tail in which no operation ran on the device, before
the gap's dispatch point, and the decoder's worker was in the tick's
jitted call: `serve.tick.dispatch`."""
from perfbench import host_gap


def read(ctx):
    return host_gap.idle_percent(ctx, "dispatch")
