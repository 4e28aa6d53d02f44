"""Device time of chord allocation, triangulation and the CSR splice
(``repro.separation.chords`` scope) per round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "separation.chords", __file__)
