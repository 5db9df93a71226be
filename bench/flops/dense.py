"""Forward FLOPs per token of a dense decoder layer: grouped-query
attention and a gated MLP.

Attention scores and their weighted sum count every key position of the
sequence (the PaLM convention, 4 * seq * heads * head_dim per token and
layer), though causal masking makes half of them zero.
"""


def layer_flops(m, seq: int) -> float:
    d, f = m["d_model"], m["d_ff"]
    nq, nkv = m["num_heads"], m["num_kv_heads"]
    h = m["head_dim"] or d // nq
    proj = 2 * d * (nq * h + 2 * nkv * h) + 2 * nq * h * d
    scores = 4 * seq * nq * h
    mlp = 2 * 3 * d * f
    return proj + scores + mlp
