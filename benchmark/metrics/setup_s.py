"""Process start to window start: imports, data from the seed, the warm-up
fit and predict (compilation, or loading programs from the cache)."""


def read(ctx):
    return ctx["setup_s"]
