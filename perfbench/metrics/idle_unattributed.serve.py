"""Share of the traced tail in which no operation ran on the device and the
decoder's worker was under none of the program's spans (nor in `serve.idle`,
waiting for a request): near nought, and says so when a span is missing."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.idle_percent(ctx, span_reduce.UNATTRIBUTED)
