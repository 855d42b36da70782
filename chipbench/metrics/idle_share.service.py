"""Device idle share of the service window (see idle_share.campaign).
Moves ``job_p95_s``."""
from bench import devtrace


def read(ctx):
    return devtrace.idle_percent(ctx.trace)
