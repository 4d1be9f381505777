"""Median over the window's finished streamed requests of the time from
their last token to the end of their stream: the program's span
`serve.request`, its duration less its attribute `last_token_s`. None on a
program whose request spans carry no `last_token_s`."""
import statistics

from perfbench import span_reduce


def read(ctx):
    spans = span_reduce.window_spans(ctx, "serve.request")
    tails = [s["duration_s"] - s["attrs"]["last_token_s"]
             for s in spans or () if "last_token_s" in s["attrs"]]
    return 1e3 * statistics.median(tails) if tails else None
