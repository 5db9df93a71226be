"""Device time per traced step of the operations under the model's ``mla``
scope: the latent attention's projections before the kernels: the query
projection, the latent down-projection, its norm, the up-projection to
per-head k and v, RoPE and the shared rotary key's assembly into each
head's k."""


def read(ctx):
    busy = ctx.in_scope("mla")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
