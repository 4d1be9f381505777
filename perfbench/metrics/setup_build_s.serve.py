"""The part of `setup_s` before the first warm-up request: process start to
the engine up, which is imports, the weights made from the seed, the model
built on them and the engine's start (the job's own stamps, host clock).
With `setup_warm_s.serve` it adds up to `setup_s`."""


def read(ctx):
    setup = ctx.get("setup")
    if not setup:
        return None
    return setup["imports_weights_s"] + setup["engine_s"]
