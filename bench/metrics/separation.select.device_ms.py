"""Device time of the repulsive-edge top-k (``repro.separation.select``
scope) per round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "separation.select", __file__)
