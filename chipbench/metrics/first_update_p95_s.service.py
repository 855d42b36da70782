"""95th percentile, over the jobs due in the window, of the time from a
job's due time to the first streamed update on its ticket, on the
benchmark's client clock.  Moves ``job_p95_s``."""
from bench.service import percentile


def read(ctx):
    x = ctx.runner.first_updates()
    return percentile(x, 95) if x.size else None
