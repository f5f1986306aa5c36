"""Whole step: useful model FLOPs of every prefill and decode token in the
window (``servebench.work``) over the traced window times the chip's
bf16 peak (%)."""


def read(ctx):
    if ctx.trace is None:
        return None
    w, c = ctx.work, ctx.config
    flops = sum(w.prefill_flops(c, L) for _, L in ctx.host.prefills)
    flops += sum(w.decode_flops(c, keys) for _, keys in ctx.host.decode_steps)
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops_per_s"])
