"""Device time of the segment programs per generation: the trace's time
in the segment programs' modules over the generations that the traced
segments ran.  Moves ``evals_per_s``."""
from bench import devtrace


def read(ctx):
    t = devtrace.module_seconds(ctx.trace, devtrace.SEGMENT_MODULE)
    gens = ctx.window.get("traced_generations", 0)
    return 1e3 * t / gens if t and gens else None
