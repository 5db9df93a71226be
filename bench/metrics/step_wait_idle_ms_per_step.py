"""Device idle time per traced step that fell inside the loop's
``sysom.loop.step_wait``: the wait on the step's result."""


def read(ctx):
    idle = ctx.idle_s_by_span.get("step_wait")
    if idle is None or ctx.steps <= 0:
        return None
    return 1e3 * idle / ctx.steps
