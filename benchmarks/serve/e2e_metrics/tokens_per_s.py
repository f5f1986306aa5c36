"""Every output token produced in the window, over the window."""


def read(ctx):
    log = ctx.host
    n = sum(len(r.stamps) for r in log.requests)
    return n / log.window_s if n else None
