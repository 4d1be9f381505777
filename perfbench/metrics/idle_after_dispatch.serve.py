"""Share of the traced tail in which no operation ran on the device though
the program that ended the gap was already handed over: the whole of a gap
that no dispatch call (`serve.tick.dispatch`, `serve.admit.dispatch`)
overlaps, and the rest of a gap from its dispatch point (the end of the
first call that overlaps it) on. Dispatching earlier cannot shorten it."""
from perfbench import host_gap


def read(ctx):
    return host_gap.idle_percent(ctx, "after_dispatch")
