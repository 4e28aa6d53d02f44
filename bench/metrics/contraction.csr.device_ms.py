"""Device time of the contracted CSR (``repro.contraction.csr`` scope:
``row_ptr``'s searchsorted and the partner bisect) per round, ms."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "contraction.csr", __file__)
