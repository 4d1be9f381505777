"""Share of the traced tail in which no operation ran on the device, before
the gap's dispatch point, and the decoder's worker was booking:
`serve.sweep`, `serve.tick.plan`, `serve.tick.emit` outside its readback,
`serve.tick.deliver`, and `serve.batch` or `serve.tick.stage` outside their
children."""
from perfbench import host_gap


def read(ctx):
    return host_gap.idle_percent(ctx, "booking")
