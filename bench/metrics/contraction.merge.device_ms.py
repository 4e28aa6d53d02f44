"""Device time of the parallel-edge merge (``repro.contraction.merge``
scope: the 2E lexsort, run compaction, COO scatter and cost sum) per
round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "contraction.merge", __file__)
