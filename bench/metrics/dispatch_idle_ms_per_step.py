"""Device idle time per traced step that fell inside the loop's
``sysom.loop.next_batch`` and ``sysom.loop.dispatch``: input and the
step's launch."""


def read(ctx):
    idle = ctx.idle_s_by_span.get("dispatch")
    if idle is None or ctx.steps <= 0:
        return None
    return 1e3 * idle / ctx.steps
