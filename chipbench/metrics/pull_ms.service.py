"""Mean host time of the server's blocking pull at an island boundary:
the program's ``service_boundary_pull_s`` histogram, sum over count, in
the window.  Moves ``job_p95_s``."""


def read(ctx):
    s, n = ctx.counters.get("service_boundary_pull_s", (0.0, 0))
    return 1e3 * s / n if n else None
