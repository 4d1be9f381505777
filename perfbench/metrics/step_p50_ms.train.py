"""Median host time from one optimizer step's loss being ready to the
next's, over the window (one step in flight, so it is the device's step)."""
import statistics


def read(ctx):
    return 1e3 * statistics.median(ctx["window"]["step_seconds"])
