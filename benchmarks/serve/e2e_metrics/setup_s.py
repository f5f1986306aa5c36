"""Process start to window start: imports, engine build, weights,
compilation (or loading it from the cache) and warm-up."""


def read(ctx):
    return ctx.setup_s
