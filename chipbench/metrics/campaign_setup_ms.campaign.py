"""Mean host time of a campaign's entry before its first boundary: the
keys, the instances and the init (the program's ``keys``, ``instances``
and ``init`` phases), from its ``bucketed_campaign_setup_s`` histogram,
sum over count, in the window.  Moves ``evals_per_s``."""


def read(ctx):
    s, n = ctx.counters.get("bucketed_campaign_setup_s", (0.0, 0))
    return 1e3 * s / n if n else None
