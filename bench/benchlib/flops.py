"""Model FLOPs of one training step, from a configuration's sizes.

Forward plus backward, counted as three forward passes; recomputation
(rematerialisation) is not counted.  A matrix product of (m, k) by (k, n)
is 2mkn.  Elementwise work, norms and the softmax are not counted.

- Attention scores and their weighted sum count every key position of
  the sequence (the PaLM convention, 4 * seq * heads * head_dim per token
  and layer), though causal masking makes half of them zero.
- The vocabulary head counts the real vocabulary, not its padding.
- A Mamba-2 layer's scan counts the chunked state-space-dual algorithm at
  the configuration's chunk length Q: per token 2Qn for C B^T (one group),
  and per head 2Qp for the masked mixing, 2pn for the chunk state and 2pn
  for the state's output.
"""
from __future__ import annotations


def _dense_layer(m, seq: int) -> float:
    d, f = m["d_model"], m["d_ff"]
    nq, nkv = m["num_heads"], m["num_kv_heads"]
    h = m["head_dim"] or d // nq
    proj = 2 * d * (nq * h + 2 * nkv * h) + 2 * nq * h * d
    scores = 4 * seq * nq * h
    mlp = 2 * 3 * d * f
    return proj + scores + mlp


def _mamba2_layer(m, seq: int) -> float:
    d = m["d_model"]
    di = m["ssm_expand"] * d
    n, p = m["ssm_state_size"], m["ssm_head_dim"]
    nh = di // p
    q = min(m["ssm_chunk_size"], seq)
    proj = 2 * d * (2 * di + 2 * n + nh) + 2 * di * d
    conv = 2 * m["ssm_conv_width"] * (di + 2 * n)
    scan = 2 * q * n + nh * (2 * q * p + 4 * p * n)
    return proj + conv + scan


LAYERS = {"dense": _dense_layer, "ssm": _mamba2_layer}


def forward_flops_per_token(m, seq: int) -> float:
    layer = LAYERS[m["family"]](m, seq)
    head = 2 * m["d_model"] * m["vocab_size"]
    return m["num_layers"] * layer + head


def train_step_flops(m, batch: int, seq: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq) * batch * seq
