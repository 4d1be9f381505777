"""Share of the prompt blocks looked up in the prefix cache that were found
there, over the window (/metrics prefix_hits over prefix_lookups)."""


def read(ctx):
    c = ctx["counters"]
    if c["prefix_lookups"] <= 0:
        return None
    return 100.0 * c["prefix_hits"] / c["prefix_lookups"]
