"""Share of the rows the segment programs evaluated that were padding:
(rows − useful) / rows, with rows the program's
``bucketed_padded_evals_total`` (members × generations × bucket width)
and useful its ``bucketed_useful_evals_total``, in the window.  Moves
``evals_per_s``."""


def read(ctx):
    rows = ctx.counters.get("bucketed_padded_evals_total", 0.0)
    useful = ctx.counters.get("bucketed_useful_evals_total", 0.0)
    return 100.0 * (rows - useful) / rows if rows else None
