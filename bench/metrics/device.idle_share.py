"""Share of the traced window in which no op ran on the device, in %:
1 - busy / window."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
