"""The grouped expert kernels' share of their roofline: the least time the
chip could take for the routed experts' products, the larger of their
FLOPs over the peak and their bytes over the HBM bandwidth (``peaks``),
over the device time of the Pallas kernels under the model's ``experts``
scope (megablox ``gmm`` and ``tgmm``).  None where no such kernel runs.

The work is counted for the (token, expert) pairs a chip would see with
the routing even: T * k * held / E of them a layer, T the step's tokens.
Routing is not even, and the kernels do the pairs they are given, so an
uneven step moves this share as well as the kernels' speed.  Each of the
three products (gate, in, out) is counted once forward and twice backward
(the input's gradient and the weights'), no recomputation; each takes its
rows, its experts' weights and gives its rows once, at the compute type.
"""
import jax.numpy as jnp

from benchlib import peaks, programtrace


def _sizes(m, batch: int, seq: int):
    pairs = batch * seq * m["num_experts_per_tok"] * m["experts_held"] \
        / m["num_experts"]
    return pairs, m["d_model"], m["d_ff"], m["num_layers"] - m[
        "first_dense_layers"]


def flops(m, batch: int, seq: int) -> float:
    pairs, d, f, layers = _sizes(m, batch, seq)
    return 9 * 2 * pairs * d * f * layers


def bytes_moved(m, batch: int, seq: int) -> float:
    """Per pass of each product: its rows in (pairs x d or f), its weights
    (held x d x f) and its rows out; three products, three passes."""
    pairs, d, f, layers = _sizes(m, batch, seq)
    weights = m["experts_held"] * d * f
    return 9 * (pairs * d + pairs * f + weights) * jnp.dtype(
        m["compute_dtype"]).itemsize * layers


def kernel_s(ctx) -> float:
    """Busy seconds of the Pallas kernels under ``experts``."""
    def kernel(op):
        names = programtrace.components(ctx.op_path.get(op, ""))
        return names[-1] == "pallas_call" and "experts" in names
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
