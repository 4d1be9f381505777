"""Share of the traced tail in which no operation ran on the device and the
decoder's worker was in its loop outside admission: `serve.sweep`,
`serve.tick.plan`, `serve.tick.stage`, `serve.tick.wait` (`serve.batch` around
the last two) or `serve.tick.emit`."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.idle_percent(ctx, "tick")
