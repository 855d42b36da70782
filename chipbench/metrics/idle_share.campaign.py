"""Device idle share of the campaign window: 1 − (union of the intervals
in which an operation ran on the device) / window, averaged over the
cell's devices, from the profiler trace.  Moves ``evals_per_s``."""
from bench import devtrace


def read(ctx):
    return devtrace.idle_percent(ctx.trace)
