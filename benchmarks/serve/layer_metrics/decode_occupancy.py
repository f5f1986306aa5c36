"""Scheduler loop: mean share of the decode slots live per decode step in
the window, as the engine's ``on_decode`` hook reports them (%)."""


def read(ctx):
    steps = ctx.host.decode_steps
    if not steps:
        return None
    live = sum(len(keys) for _, keys in steps)
    return 100.0 * live / (len(steps) * ctx.decode_slots)
