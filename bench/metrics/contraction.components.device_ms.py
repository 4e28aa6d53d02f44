"""Device time of the contraction's own connected components, root
ranking and relabelling (``repro.contraction.components`` scope) per
round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "contraction.components", __file__)
