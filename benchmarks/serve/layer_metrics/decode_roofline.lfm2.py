"""Model step against the chip, for an LFM2 MoE configuration: the least
time the decode steps of the window need for their useful FLOPs and
bytes (``servebench.work_lfm2_moe``, from the live slots' positions and
the live arrays' dtypes) over their device time in the trace (%)."""
from servebench import work, work_lfm2_moe


def read(ctx):
    if ctx.trace is None or not ctx.host.decode_steps:
        return None
    dev = ctx.trace.step_times("decode")
    if not dev:
        return None
    c, w = ctx.config, work_lfm2_moe
    least = 0.0
    bounds = {"compute": 0, "memory": 0}
    for _, keys in ctx.host.decode_steps:
        t, bound = work.least_time(
            w.decode_flops(c, keys),
            w.decode_bytes(c, keys, ctx.param_itemsize, ctx.kv_itemsize),
            ctx.peak)
        least += t
        bounds[bound] += 1
    ctx.notes["decode_roofline_bound"] = bounds
    mean_least = least / len(ctx.host.decode_steps)
    return 100.0 * mean_least / (sum(dev) / len(dev))
