"""Time the step thread spent in ``NodeAgent.submit`` and ``flush``, per
traced step."""


def read(ctx):
    if not ctx.agent or ctx.steps <= 0:
        return None
    spent = sum(ctx.timings["agent_submit"]) + sum(ctx.timings["agent_flush"])
    return 1e3 * spent / ctx.steps
