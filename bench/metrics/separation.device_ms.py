"""Device time of separation (``repro.separation`` scope) per round, ms."""


def read(ctx):
    return ctx.per_round_ms("separation")
