"""Device time of the 3-cycle search outside its top-k and long bucket
(``repro.separation.triangles`` scope: the short bucket and the bucket
combine) per round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "separation.triangles", __file__)
