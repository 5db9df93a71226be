"""Device busy time per step in the traced stretch."""


def read(ctx):
    if ctx.busy_s is None or ctx.steps <= 0:
        return None
    return 1e3 * ctx.busy_s / ctx.steps
