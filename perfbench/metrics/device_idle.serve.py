"""Share of the traced seconds of the steady window in which no operation ran on the device."""


def read(ctx):
    return ctx["trace"].idle_percent(ctx["traced"]["window_s"])
