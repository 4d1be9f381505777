"""95th percentile over the window's admissions of the time a request spent
in the decoder's queue, from submit (or from the preemption that put it
back) to the pick: the program's span `serve.queue`."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.duration_ms(ctx, "serve.queue", 95)
