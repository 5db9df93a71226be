"""Mamba2 block via SSD (state-space duality), chunked scan formulation.

Reference math follows arXiv:2405.21060 (listing 1), with the inter-chunk
recurrence expressed as a ``lax.scan`` (TPU-friendly) instead of a second
segsum.  The chunk-local quadratic part comes from the Pallas kernel
(repro.kernels.ssd) or its pure-jnp oracle (repro.kernels.ref), which is
also the dry-run path.

Shapes: x (B, S, H, P) heads x head_dim; A (H,); B/C (B, S, N) (ngroups=1);
dt (B, S, H).  State: (B, H, P, N).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, B, C, chunk: int,
                initial_state=None,
                use_pallas: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan.  Returns (y, final_state).

    x: (b, s, h, p)   dt: (b, s, h)   A: (h,) (negative decay rates)
    B, C: (b, s, n)   state: (b, h, p, n)

    ``use_pallas`` routes the chunk-local quadratic term through the Pallas
    TPU kernel (repro.kernels.ssd) instead of its jnp oracle; the
    inter-chunk recurrence stays a lax.scan either way.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    nc = s // chunk
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"

    f32 = jnp.float32
    # head-major chunks: the chunk-term contract of repro.kernels
    xc = jnp.moveaxis(x.reshape(b, nc, chunk, h, p), 3, 2).astype(f32)
    dtc = jnp.moveaxis(dt.reshape(b, nc, chunk, h), 3, 2).astype(f32)
    Bc = B.reshape(b, nc, chunk, n).astype(f32)
    Cc = C.reshape(b, nc, chunk, n).astype(f32)

    # ---- intra-chunk (quadratic attention-like) term + chunk states ------
    from repro.kernels import ops as kernel_ops
    chunk_terms = kernel_ops.ssd_chunk if use_pallas else kernel_ops.ssd_chunk_ref
    y_diag, states, in_decay = chunk_terms(xc, dtc, A, Bc, Cc)
    # y_diag (b,nc,h,l,p); states (b,nc,h,p,n); in_decay (b,nc,h,l)

    # ---- inter-chunk recurrence (scan over chunks) -----------------------
    chunk_decay = in_decay[..., -1]                     # (b,nc,h)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), dtype=f32)
    else:
        initial_state = initial_state.astype(f32)

    def step(carry, inp):
        st_in, decay, st_chunk = carry, inp[0], inp[1]
        st_out = st_in * decay[..., None, None] + st_chunk
        return st_out, st_in  # emit the state *entering* the chunk

    xs = (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0))
    final_state, entry_states = jax.lax.scan(step, initial_state, xs)
    entry_states = jnp.moveaxis(entry_states, 0, 1)     # (b,nc,h,p,n)

    # ---- off-diagonal contribution from carried state --------------------
    y_off = jnp.einsum("bcln,bchl,bchpn->bchlp", Cc, in_decay, entry_states)

    y = jnp.moveaxis(y_diag.astype(f32) + y_off, 2, 3).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """Single-token recurrence.  state (b,h,p,n); x_t (b,h,p); dt_t (b,h);
    B_t/C_t (b,n).  Returns (y_t, new_state)."""
    f32 = jnp.float32
    state = state.astype(f32)
    dA = jnp.exp(dt_t.astype(f32) * A.astype(f32))          # (b,h)
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt_t.astype(f32), B_t.astype(f32),
                     x_t.astype(f32))
    new_state = state * dA[..., None, None] + dBx
    y = jnp.einsum("bn,bhpn->bhp", C_t.astype(f32), new_state)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# Mamba2 block (in_proj -> conv -> SSD -> gated norm -> out_proj)
# ---------------------------------------------------------------------------


def init_mamba2_block(key, cfg: ModelConfig):
    d = cfg.d_model
    di = cfg.ssm_d_inner
    nh = cfg.ssm_num_heads
    ns = cfg.ssm_state_size
    conv_ch = di + 2 * ns   # x, B, C share the causal conv
    ks = jax.random.split(key, 5)
    return {
        "in_proj": layers.dense_init(
            ks[0], (d, 2 * di + 2 * ns + nh), ("embed", "ssm_inner"), cfg),
        "conv_w": layers.dense_init(
            ks[1], (cfg.ssm_conv_width, conv_ch), ("conv", "ssm_inner"), cfg,
            fan_in=cfg.ssm_conv_width),
        "conv_b": layers.zeros_init((conv_ch,), ("ssm_inner",), cfg),
        "A_log": (jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32))
                  .astype(jnp.dtype(cfg.param_dtype)), ("ssm_heads",)),
        "D": layers.ones_init((nh,), ("ssm_heads",), cfg),
        "dt_bias": layers.zeros_init((nh,), ("ssm_heads",), cfg),
        "norm": layers.init_rms_norm(di, cfg),
        "out_proj": layers.dense_init(ks[4], (di, d), ("ssm_inner", "embed"),
                                      cfg, fan_in=di),
    }


def _split_proj(proj, cfg: ModelConfig):
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state_size, cfg.ssm_num_heads
    z, xbc_dt = jnp.split(proj, [di], axis=-1)
    xbc, dt_raw = jnp.split(xbc_dt, [di + 2 * ns], axis=-1)
    return z, xbc, dt_raw


def mamba2_block(params, x, cfg: ModelConfig, conv_state=None, ssm_state=None):
    """Full-sequence Mamba2 block.  x: (B, S, d) -> (B, S, d).

    When conv_state/ssm_state are given, they are consumed and the updated
    states are returned (prefill-with-state); otherwise zeros are assumed.
    """
    b, s, d = x.shape
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state_size, cfg.ssm_num_heads
    hp = cfg.ssm_head_dim

    proj = x @ params["in_proj"]
    z, xbc, dt_raw = _split_proj(proj, cfg)

    # causal depthwise conv over seq (width W)
    w = params["conv_w"]                                  # (W, C)
    W = w.shape[0]
    pad = jnp.zeros((b, W - 1, xbc.shape[-1]), xbc.dtype) if conv_state is None else conv_state
    xbc_p = jnp.concatenate([pad, xbc], axis=1)
    conv = sum(xbc_p[:, i:i + s] * w[i] for i in range(W))
    conv = jax.nn.silu(conv + params["conv_b"])
    new_conv_state = xbc_p[:, -(W - 1):] if W > 1 else jnp.zeros((b, 0, xbc.shape[-1]), xbc.dtype)

    xs, B, C = jnp.split(conv, [di, di + ns], axis=-1)
    xh = xs.reshape(b, s, nh, hp)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    chunk = min(cfg.ssm_chunk_size, s)
    y, final_state = ssd_chunked(xh, dt, A, B, C, chunk,
                                 initial_state=ssm_state,
                                 use_pallas=cfg.use_pallas)
    y = y + xh * params["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(b, s, di)

    y = layers.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                        params["norm"], cfg.norm_eps)
    out = y @ params["out_proj"]
    return out, (new_conv_state, final_state)


def mamba2_decode(params, x, cfg: ModelConfig, conv_state, ssm_state):
    """One-token decode.  x: (B, 1, d); conv_state (B, W-1, C);
    ssm_state (B, H, P, N)."""
    b = x.shape[0]
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state_size, cfg.ssm_num_heads
    hp = cfg.ssm_head_dim

    proj = x[:, 0] @ params["in_proj"]                    # (B, ...)
    z, xbc, dt_raw = _split_proj(proj, cfg)

    w = params["conv_w"]
    W = w.shape[0]
    window = jnp.concatenate([conv_state, xbc[:, None]], axis=1)  # (B, W, C)
    conv = jnp.einsum("bwc,wc->bc", window, w)
    conv = jax.nn.silu(conv + params["conv_b"])
    new_conv_state = window[:, 1:]

    xs, B, C = jnp.split(conv, [di, di + ns], axis=-1)
    xh = xs.reshape(b, nh, hp)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    y, new_ssm = ssd_decode_step(ssm_state, xh, dt, A, B, C)
    y = y + xh * params["D"].astype(y.dtype)[None, :, None]
    y = y.reshape(b, di)
    y = layers.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                        params["norm"], cfg.norm_eps)
    out = (y @ params["out_proj"])[:, None]
    return out, (new_conv_state, new_ssm)
