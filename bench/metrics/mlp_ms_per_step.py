"""Device time per traced step of the operations under the model's ``mlp``
scope: the pre-FFN norm and the gated MLP."""


def read(ctx):
    busy = ctx.in_scope("mlp")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
