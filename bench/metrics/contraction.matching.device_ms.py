"""Device time of the matching (``repro.contraction.matching`` scope:
the handshake rounds of ``maximum_matching``) per round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "contraction.matching", __file__)
