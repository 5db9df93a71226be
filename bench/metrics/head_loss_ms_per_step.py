"""Device time per traced step of the operations under the model's
``head_loss`` scope: the vocabulary head and its cross-entropy."""


def read(ctx):
    busy = ctx.in_scope("head_loss")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
