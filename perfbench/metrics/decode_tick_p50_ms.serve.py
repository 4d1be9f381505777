"""Median of the program's own span around one paged decode tick and its
readback (`serve.batch`, kind `decode.paged`) over the window."""
import statistics


def read(ctx):
    spans = ctx["spans"]
    if not spans:
        return None
    return 1e3 * statistics.median(s["duration_s"] for s in spans)
