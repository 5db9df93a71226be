"""Device time per traced step of the operations under the model's
``router`` scope: the router's float32 logits and sigmoid, the biased
top-k, the renormalised weights, the balance loss and the per-expert
token count."""


def read(ctx):
    busy = ctx.in_scope("router")
    if ctx.steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / ctx.steps
