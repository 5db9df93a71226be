"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU; identical kernel code targets TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


FA_CASES = [
    # (b, hq, hkv, s, d, causal, window, dtype, tol)
    (1, 2, 2, 128, 64, True, 0, jnp.float32, 2e-5),
    (2, 4, 2, 256, 64, True, 0, jnp.float32, 2e-5),
    (1, 8, 1, 128, 32, True, 64, jnp.float32, 2e-5),    # MQA + SWA
    (2, 2, 2, 256, 128, False, 0, jnp.float32, 2e-5),   # bidirectional
    (1, 4, 4, 512, 64, True, 128, jnp.float32, 2e-5),
    (1, 4, 2, 256, 64, True, 0, jnp.bfloat16, 2e-2),
    (1, 2, 1, 128, 128, True, 0, jnp.bfloat16, 2e-2),
]


@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window,dtype,tol", FA_CASES)
def test_flash_attention_sweep(b, hq, hkv, s, d, causal, window, dtype, tol):
    q = _rand((b, hq, s, d), dtype)
    k = _rand((b, hkv, s, d), dtype)
    v = _rand((b, hkv, s, d), dtype)
    out = ops.flash_attention_bhsd(q, k, v, causal=causal,
                                   sliding_window=window)
    ref = ops.flash_attention_ref(q, k, v, causal=causal,
                                  sliding_window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_block_shapes():
    """Non-default block shapes must not change results."""
    from repro.kernels.flash_attention import flash_attention_fwd
    q = _rand((1, 2, 256, 64), jnp.float32)
    k = _rand((1, 2, 256, 64), jnp.float32)
    v = _rand((1, 2, 256, 64), jnp.float32)
    base = flash_attention_fwd(q, k, v, block_q=128, block_k=128,
                               interpret=True)
    for bq, bk in [(64, 64), (256, 64), (64, 256), (32, 128)]:
        out = flash_attention_fwd(q, k, v, block_q=bq, block_k=bk,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=2e-5, rtol=2e-5)


def test_pallas_backend_choice(monkeypatch):
    """CPU interprets, TPU compiles, anything else raises at trace time."""
    assert ops.pallas_interpret() is True          # the suite runs on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops.pallas_interpret()
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops.rmsnorm(jnp.ones((8, 24)), jnp.zeros((24,)))   # traced now


SSD_CASES = [
    # (b, nc, L, h, p, n, dtype, tol)
    (1, 2, 32, 2, 16, 8, jnp.float32, 1e-4),
    (2, 3, 64, 4, 32, 16, jnp.float32, 1e-4),
    (1, 4, 128, 2, 64, 32, jnp.float32, 2e-4),
    (1, 2, 64, 4, 32, 16, jnp.bfloat16, 5e-2),
]


@pytest.mark.parametrize("b,nc,L,h,p,n,dtype,tol", SSD_CASES)
def test_ssd_chunk_sweep(b, nc, L, h, p, n, dtype, tol):
    x = _rand((b, nc, h, L, p), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, nc, h, L)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = _rand((b, nc, L, n), dtype)
    C = _rand((b, nc, L, n), dtype)
    yk, stk, idk = ops.ssd_chunk(x, dt, A, B, C)
    yr, str_, idr = ops.ssd_chunk_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(stk), np.asarray(str_),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(idk), np.asarray(idr), atol=1e-5)
    # the whole-chunk decay is the in-chunk decay at the last position
    np.testing.assert_allclose(
        np.asarray(idk[..., -1]),
        np.exp(np.asarray(dt).sum(-1) * np.asarray(A)), rtol=1e-5)


@pytest.mark.parametrize("rows,d,dtype,tol", [
    (64, 128, jnp.float32, 1e-5),
    (256, 512, jnp.float32, 1e-5),
    (128, 256, jnp.bfloat16, 2e-2),
    (512, 64, jnp.float32, 1e-5),
])
def test_rmsnorm_sweep(rows, d, dtype, tol):
    x = _rand((rows, d), dtype)
    w = _rand((d,), jnp.float32) * 0.1
    out = ops.rmsnorm(x, w)
    ref = ops.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_ssd_kernel_consistent_with_full_scan():
    """Kernel chunk terms + host recurrence == monolithic jnp SSD."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n, chunk = 1, 128, 2, 16, 8, 32
    x = _rand((b, s, h, p), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = _rand((b, s, n), jnp.float32)
    C = _rand((b, s, n), jnp.float32)
    y_ref, st_ref = ssd_chunked(x, dt, A, B, C, chunk, use_pallas=False)
    y_k, st_k = ssd_chunked(x, dt, A, B, C, chunk, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_ref),
                               atol=1e-4, rtol=1e-4)


def test_ssd_chunked_gradient_finite():
    """Strong decay over a long chunk overflows exp() above the diagonal;
    the reference path must still give finite gradients (it trains)."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n, chunk = 1, 128, 2, 8, 4, 128
    x = _rand((b, s, h, p), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.5, 1.0, (b, s, h)), jnp.float32)
    A = jnp.asarray([-8.0, -16.0], jnp.float32)
    B = _rand((b, s, n), jnp.float32)
    C = _rand((b, s, n), jnp.float32)
    grads = jax.grad(lambda x, dt: jnp.sum(ssd_chunked(
        x, dt, A, B, C, chunk)[0] ** 2), argnums=(0, 1))(x, dt)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_ssd_decode_matches_chunked():
    """Sequential ssd_decode_step over S tokens == chunked scan output."""
    from repro.models.ssm import ssd_chunked, ssd_decode_step
    b, s, h, p, n = 1, 16, 2, 8, 4
    x = _rand((b, s, h, p), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.05, 0.3, (b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 1.5, (h,)), jnp.float32)
    B = _rand((b, s, n), jnp.float32)
    C = _rand((b, s, n), jnp.float32)
    y_chunk, final = ssd_chunked(x, dt, A, B, C, chunk=8)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y_t, state = ssd_decode_step(state, x[:, t], dt[:, t], A,
                                     B[:, t], C[:, t])
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_chunk),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(final),
                               atol=1e-4, rtol=1e-3)
