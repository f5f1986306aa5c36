"""Kernels: the ``moe_gmm`` grouped expert matmul in the decode step
against the chip: the least time its calls of one step need for their
FLOPs and bytes (``servebench.work_lfm2_moe.moe_gmm_calls``) over its
device time per decode call, the summed ``jit_decode/moe_gmm*`` ops of
the trace over the number of ``jit_decode`` calls (%). Needs every one of
the kernel's ops (``work_lfm2_moe.moe_gmm_ops``) among the trace's largest:
with one missing the sum would fall short, so there is nothing to read, as
there is in a program without the kernel."""
from servebench import work, work_lfm2_moe


def read(ctx):
    if ctx.trace is None:
        return None
    ops = [s for name, s in ctx.trace.top_ops
           if name.startswith("jit_decode/moe_gmm")]
    calls = len(ctx.trace.step_times("decode"))
    if len(ops) != work_lfm2_moe.moe_gmm_ops(ctx.config) or not calls:
        return None
    secs = sum(ops)
    least = 0.0
    for flops, nbytes in work_lfm2_moe.moe_gmm_calls(
            ctx.config, ctx.decode_slots, ctx.param_itemsize):
        least += work.least_time(flops, nbytes, ctx.peak)[0]
    return 100.0 * least / (secs / calls)
