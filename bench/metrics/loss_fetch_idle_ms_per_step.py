"""Device idle time per traced step that fell inside the loop's
``sysom.loop.loss_fetch``: the loss's transfer to the host."""


def read(ctx):
    idle = ctx.idle_s_by_span.get("loss_fetch")
    if idle is None or ctx.steps <= 0:
        return None
    return 1e3 * idle / ctx.steps
