"""Forward FLOPs per token of a Mamba-2 layer.

The scan counts the chunked state-space-dual algorithm at the
configuration's chunk length Q: per token 2Qn for C B^T (one group), and
per head 2Qp for the masked mixing, 2pn for the chunk state and 2pn for
the state's output.
"""


def layer_flops(m, seq: int) -> float:
    d = m["d_model"]
    di = m["ssm_expand"] * d
    n, p = m["ssm_state_size"], m["ssm_head_dim"]
    nh = di // p
    q = min(m["ssm_chunk_size"], seq)
    proj = 2 * d * (2 * di + 2 * n + nh) + 2 * di * d
    conv = 2 * m["ssm_conv_width"] * (di + 2 * n)
    scan = 2 * q * n + nh * (2 * q * p + 4 * p * n)
    return proj + conv + scan
