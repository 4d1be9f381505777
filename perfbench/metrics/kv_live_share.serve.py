"""Share of what the decode tick's attention reads that is live: over the
window's ticks (`serve.batch`, kind `decode.paged`), 100 x the sum of
`kv_live` (positions the active lanes can see) over the sum of `kv_read`
(positions the program loops over for every lane, up to the longest live
one). Nothing where a tick lacks the attributes, as on a program that
attends `max_len` whatever is live."""


def read(ctx):
    spans = ctx.get("spans") or []
    if not spans or any("kv_live" not in s["attrs"]
                        or "kv_read" not in s["attrs"] for s in spans):
        return None
    read_ = sum(s["attrs"]["kv_read"] for s in spans)
    if read_ <= 0:
        return None
    return 100.0 * sum(s["attrs"]["kv_live"] for s in spans) / read_
