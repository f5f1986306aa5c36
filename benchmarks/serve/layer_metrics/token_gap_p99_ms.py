"""Scheduler loop: p99 of the gaps between successive tokens of every
request in the window, from the program's own stamps
(``ServeRequest.token_t``: the host clock when each token's value reached
the host), in ms. The in-program counterpart of ``itl_p99_ms``, which
stamps a token at the end of its engine iteration. Read in the traced
run, so it carries the profiler's cost."""


def read(ctx):
    gaps = []
    for r in ctx.host.requests:
        stamps = getattr(r.req, "token_t", None) or []
        gaps.extend(b - a for a, b in zip(stamps, stamps[1:]))
    v = ctx.pct(gaps, 99)
    return None if v is None else 1e3 * v
