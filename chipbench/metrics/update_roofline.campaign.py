"""Roofline share of the fused update kernel ``cma_gen_update``: the least
time the chip could take for its work at each running member's own n and
λ (``peaks.cma_gen_update_work``; the larger of operations over peak
FLOP/s and bytes over peak HBM bandwidth) over the kernel's device time
in the trace.  Moves ``evals_per_s``."""
from bench import devtrace, peaks
from bench.common import log


def read(ctx):
    t = devtrace.category_seconds(ctx.trace, "cma_gen_update")
    if not t:
        return None
    ops, nbytes = ctx.runner.update_work()
    least, bound = peaks.roofline_time(ops, nbytes, ctx.device["kind"])
    log(f"update_roofline: {ops:.4g} operations, {nbytes:.4g} bytes, "
        f"least time {least:.4g}s ({bound} bound), kernel {t:.4g}s")
    return 100.0 * least / t
