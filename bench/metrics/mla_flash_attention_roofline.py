"""The fused attention kernels' share of their roofline in a latent
attention (MLA) model, whose query/key head is wider than its value head:
the least time the chip could take for the attention's work, the larger
of its FLOPs over the peak and its bytes over the HBM bandwidth
(``peaks``), over the device time of the Pallas kernels under the model's
``flash`` scope (``flash_attention_roofline.kernel_s``).  None where no
such kernel runs.

The work is counted the same whatever implements it (``flops``,
``bytes_moved``): of each S x S product only the causal half, and no
recomputation; and each tensor the attention takes or gives, forward and
backward, moved once: at the compute type, the log-sum-exp in float32.
k has every query head's width here (its rotary part is shared, but the
kernels read it per head).
"""
import jax.numpy as jnp

from benchlib import peaks, spec


def _dims(m):
    return (m["num_heads"], m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
            m["v_head_dim"])


def flops(m, batch: int, seq: int) -> float:
    """QK^T forward and dQ, dK backward contract or produce the query/key
    width; PV forward and dP, dV backward the value width: 2 * S * S * h
    each per head, halved by the causal mask."""
    nq, dqk, dv = _dims(m)
    return (3 * (2 * seq * seq * dqk // 2) + 3 * (2 * seq * seq * dv // 2)) \
        * batch * nq * m["num_layers"]


def bytes_moved(m, batch: int, seq: int) -> float:
    """q, k, dq and dk at the query/key width and v, the output, dO and dv
    at the value width, every head, at the compute type; the log-sum-exp
    of each query row in float32."""
    nq, dqk, dv = _dims(m)
    tensors = nq * (4 * dqk + 4 * dv) * jnp.dtype(
        m["compute_dtype"]).itemsize + nq * 4
    return batch * seq * tensors * m["num_layers"]


def read(ctx):
    busy = spec.metric_module("flash_attention_roofline",
                              ctx.cell.root).kernel_s(ctx)
    if ctx.steps <= 0 or busy <= 0:
        return None
    config = ctx.cell.config
    m, b, s = config["model"], config["batch"], config["seq_len"]
    least = max(flops(m, b, s) / peaks.peak(ctx.device_kind),
                bytes_moved(m, b, s) / peaks.peak(ctx.device_kind, "hbm_bw"))
    # busy time is a chip's; each chip does its share of the cell's steps
    return 100.0 * least * ctx.steps / ctx.cell.chips / busy
