"""Time the loop waited in ``next(pipeline)``, per traced step."""


def read(ctx):
    waits = ctx.timings["next_batch"]
    if ctx.steps <= 0 or not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
