"""Tokens a decode tick yields: the decoder's ledger, decode_tokens over
decode_ticks, as counted over the window."""


def read(ctx):
    c = ctx["counters"]
    if c["decode_ticks"] <= 0:
        return None
    return c["decode_tokens"] / c["decode_ticks"]
