"""Mean host time of a finished campaign's end: the segment traces
gathered to the host and the result built (the program's ``finish``
phase), from its ``bucketed_campaign_finish_s`` histogram, sum over
count, in the window.  Moves ``evals_per_s``."""


def read(ctx):
    s, n = ctx.counters.get("bucketed_campaign_finish_s", (0.0, 0))
    return 1e3 * s / n if n else None
