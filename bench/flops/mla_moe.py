"""Forward FLOPs per token of a latent-attention MoE decoder layer
(DeepSeek-V3 style), the mean over the stack: the leading dense layers
(``first_dense_layers``, a gated MLP of width ``dense_d_ff``) and the MoE
layers, each with its router, its shared experts and the routed experts
this chip holds (``experts_held`` of ``num_experts``): of each token's
``num_experts_per_tok`` experts, the share ``experts_held / num_experts``
is computed here.

Latent attention: the query projection, the latent down-projection
(with the shared rotary key), its up-projection to per-head k and v, the
output projection.  Its scores and weighted sum count every key position
of the sequence (the PaLM convention), at the query/key head width and
the value head width.
"""


def layer_flops(m, seq: int) -> float:
    d, nq, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    f, e, held = m["d_ff"], m["num_experts"], m["experts_held"]
    n_dense = m["first_dense_layers"]
    attention = (2 * (d * nq * (dn + dr) + d * (r + dr) + r * nq * (dn + dv)
                      + nq * dv * d)
                 + 2 * seq * nq * (dn + dr + dv))
    gated = lambda width: 2 * 3 * d * width
    moe = (2 * d * e + gated(m["num_shared_experts"] * f)
           + m["num_experts_per_tok"] * held / e * gated(f))
    total = (m["num_layers"] * attention + n_dense * gated(m["dense_d_ff"])
             + (m["num_layers"] - n_dense) * moe)
    return total / m["num_layers"]
