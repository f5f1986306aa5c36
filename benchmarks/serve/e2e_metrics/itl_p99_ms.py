"""99th percentile of every gap between successive tokens of every
request in the window."""


def read(ctx):
    gaps = [b - a for r in ctx.host.requests
            for a, b in zip(r.stamps, r.stamps[1:])]
    v = ctx.pct(gaps, 99)
    return None if v is None else 1e3 * v
