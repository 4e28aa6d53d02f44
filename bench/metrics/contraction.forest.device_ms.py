"""Device time of the spanning forest (``repro.contraction.forest``
scope: ``spanning_forest_contraction`` with its connected-components
loops) per round, ms. The forest runs in every round, kept or not."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "contraction.forest", __file__)
