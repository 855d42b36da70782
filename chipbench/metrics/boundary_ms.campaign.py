"""Mean host time of the segment driver's blocking pull at a boundary:
the program's ``bucketed_sync_s`` histogram, sum over count, in the
window.  Moves ``evals_per_s``."""


def read(ctx):
    s, n = ctx.counters.get("bucketed_sync_s", (0.0, 0))
    return 1e3 * s / n if n else None
