"""Device time per traced step of the operations under the model's
``attention`` scope: the pre-attention norm, the q/k/v and output
projections, RoPE and the fused kernels inside ``flash``."""


def read(ctx):
    busy = ctx.in_scope("attention")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
