"""Median per tick of the host's own work around it: `serve.tick.stage`
(the tick's uploads and dispatch) plus `serve.tick.emit` (unpacking,
streaming callbacks, futures), over the window."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.tick_host_ms(ctx)
