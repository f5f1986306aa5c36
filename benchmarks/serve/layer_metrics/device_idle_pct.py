"""Device: 1 - (union of device-op intervals) / traced window (%). One
reader for each split of the metric (``.serve``, ``.batch``) by the
end-to-end metric it moves."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
