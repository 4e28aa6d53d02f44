"""Device time of contraction (``repro.contraction`` scope) per round,
ms. Absent where the solve contracts nothing (mode d)."""


def read(ctx):
    return ctx.per_round_ms("contraction")
