"""Device idle time per traced step that fell inside the loop's
``sysom.loop.observe``: the agent's per-step work, its flush and the
service cycle."""


def read(ctx):
    idle = ctx.idle_s_by_span.get("observe")
    if idle is None or ctx.steps <= 0:
        return None
    return 1e3 * idle / ctx.steps
