"""The part of `setup_s` the warm-up requests take: one request at every
prompt length of `traffic.warm_lengths`, each of which compiles its programs
or reads them from the persistent cache (the job's own stamps, host clock).
With `setup_build_s.serve` it adds up to `setup_s`."""


def read(ctx):
    setup = ctx.get("setup")
    if not setup:
        return None
    return setup["warm_s"]
