"""Share of the traced steps in which no operation ran on the device."""


def read(ctx):
    return ctx["trace"].idle_percent(ctx["traced"]["window_s"])
