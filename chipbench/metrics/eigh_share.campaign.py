"""Share of the device's busy time spent in the eigendecomposition (the
operations whose ``op_name`` comes from ``eigh``: on the TPU, loops of a
blocked Jacobi solver for n ≤ 256).  Moves
``evals_per_s``."""
from bench import devtrace


def read(ctx):
    t = devtrace.category_seconds(ctx.trace, "eigh")
    busy = ctx.trace["busy_s"] if ctx.trace else 0.0
    return 100.0 * t / busy if t and busy else None
