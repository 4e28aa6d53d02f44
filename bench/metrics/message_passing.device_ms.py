"""Device time of message passing (``repro.message_passing`` scope) per
round, ms."""


def read(ctx):
    return ctx.per_round_ms("message_passing")
