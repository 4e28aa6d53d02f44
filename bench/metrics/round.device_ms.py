"""Device busy time per solver round, in ms: the union of the device's
op intervals over the traced solves, over their rounds."""


def read(ctx):
    if ctx.rounds <= 0 or ctx.busy_s <= 0:
        return None
    return 1e3 * ctx.busy_s / ctx.rounds
