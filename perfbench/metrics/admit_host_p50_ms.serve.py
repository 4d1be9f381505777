"""Median host time of one admission, from the pick to the return of the
prefill's dispatch (booking under the lock, upload, dispatch; the prefill is
not waited for): the program's span `serve.admit` over the window."""
from perfbench import span_reduce


def read(ctx):
    return span_reduce.duration_ms(ctx, "serve.admit", 50)
