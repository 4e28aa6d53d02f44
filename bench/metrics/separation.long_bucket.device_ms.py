"""Device time of the long degree bucket (``repro.separation.long_bucket``
scope, in the 3- and the 4/5-cycle search) per round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "separation.long_bucket", __file__)
