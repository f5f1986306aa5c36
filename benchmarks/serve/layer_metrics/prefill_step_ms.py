"""Model step: mean device time per call of the jitted prefill program in
the traced window."""


def read(ctx):
    if ctx.trace is None:
        return None
    ts = ctx.trace.step_times("prefill")
    return 1e3 * sum(ts) / len(ts) if ts else None
