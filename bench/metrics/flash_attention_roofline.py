"""The fused attention kernels' share of their roofline: the least time
the chip could take for the attention's work, the larger of its FLOPs
over the peak and its bytes over the HBM bandwidth (``peaks``), over the
device time of the Pallas kernels under the model's ``flash`` scope (the
instructions whose op name ends in ``pallas_call``; their layout work
left out).  None where no such kernel runs.

The work is counted the same whatever implements it (``flops``,
``bytes_moved``): of each S x S product only the causal half, and no
recomputation; and each tensor the attention takes or gives, forward and
backward, moved once: at the compute type, the log-sum-exp in float32.
"""
import jax.numpy as jnp

from benchlib import peaks, programtrace


def _heads(m):
    return m["num_heads"], m["num_kv_heads"], (m["head_dim"] or
                                               m["d_model"] // m["num_heads"])


def flops(m, batch: int, seq: int) -> float:
    """QK^T and PV forward, dV, dP, dQ and dK backward: six products of
    2 * S * S * h each per query head, halved by the causal mask."""
    nq, _, h = _heads(m)
    return 6 * (2 * seq * seq * h // 2) * batch * nq * m["num_layers"]


def bytes_moved(m, batch: int, seq: int) -> float:
    """q, the output, dO and dq (query heads) and k, v, dk and dv (key and
    value heads) at the compute type; the log-sum-exp of each query row
    in float32."""
    nq, nkv, h = _heads(m)
    tensors = (4 * nq * h + 4 * nkv * h) * jnp.dtype(
        m["compute_dtype"]).itemsize + nq * 4
    return batch * seq * tensors * m["num_layers"]


def kernel_s(ctx) -> float:
    """Busy seconds of the Pallas kernels under ``flash``."""
    def kernel(op):
        names = programtrace.components(ctx.op_path.get(op, ""))
        return names[-1] == "pallas_call" and "flash" in names
    return sum(t for op, t in ctx.device_s_by_op.items() if kernel(op))


def read(ctx):
    busy = kernel_s(ctx)
    if ctx.steps <= 0 or busy <= 0:
        return None
    config = ctx.cell.config
    m, b, s = config["model"], config["batch"], config["seq_len"]
    least = max(flops(m, b, s) / peaks.peak(ctx.device_kind),
                bytes_moved(m, b, s) / peaks.peak(ctx.device_kind, "hbm_bw"))
    # busy time is a chip's; each chip does its share of the cell's steps
    return 100.0 * least * ctx.steps / ctx.cell.chips / busy
