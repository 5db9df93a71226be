"""Device time per traced step of the operations under the model's
``optimizer`` scope: gradient clipping, the schedule and the AdamW
update."""


def read(ctx):
    busy = ctx.in_scope("optimizer")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
