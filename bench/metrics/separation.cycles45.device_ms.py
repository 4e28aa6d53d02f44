"""Device time of the 4/5-cycle candidate search outside its top-k, long
bucket and chords (``repro.separation.cycles45`` scope) per round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "separation.cycles45", __file__)
