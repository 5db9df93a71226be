"""Device time per traced step of the operations under the model's
``experts`` scope: the routed experts this chip holds: the sort of
(token, expert) pairs by expert, the grouped products and the weighted
combine."""


def read(ctx):
    busy = ctx.in_scope("experts")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
