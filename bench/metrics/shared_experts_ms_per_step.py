"""Device time per traced step of the operations under the model's
``shared_experts`` scope: the shared experts, one gated MLP over every
token."""


def read(ctx):
    busy = ctx.in_scope("shared_experts")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
