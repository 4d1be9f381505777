"""Share of the traced tail in which no operation ran on the device and the
decoder's worker was inside `serve.admit` (booking, upload, dispatch)."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.idle_percent(ctx, "admit")
