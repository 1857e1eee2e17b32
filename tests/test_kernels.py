"""Per-kernel correctness: Pallas (interpret mode) and XLA paths vs the
pure-jnp oracles, swept over shapes/dtypes; gradients vs autodiff-through-ref.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import (flash_attention,
                                                  flash_attention_fwd)
from repro.kernels.flash_attention.ops import _mha_xla, decode_mha
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.flash_decode.kernel import flash_decode_pallas
from repro.kernels.flash_decode.ref import flash_decode_ref
from repro.kernels.mamba_scan.ops import _mamba_xla, mamba_decode_step
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.rwkv6_scan.ops import _rwkv6_xla, rwkv6_decode_step
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


ATTN_CASES = [
    # B, S, T, H, KV, D, causal, window, softcap
    (2, 128, 128, 4, 2, 64, True, 0, 0.0),
    (1, 100, 100, 4, 4, 32, True, 48, 50.0),     # ragged + window + softcap
    (2, 64, 256, 8, 2, 64, True, 0, 0.0),        # cross-size (q_offset)
    (1, 64, 64, 2, 1, 128, False, 0, 0.0),       # bidirectional (encoder)
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_pallas_vs_ref(case, dtype):
    B, S, T, H, KV, D, causal, window, softcap = case
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, S, H, D), dtype)
    k = rand(ks[1], (B, T, KV, D), dtype)
    v = rand(ks[2], (B, T, KV, D), dtype)
    qoff = T - S if causal else 0
    ref = mha_ref(q, k, v, causal=causal, window=window, softcap=softcap,
                  q_offset=qoff)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_offset=qoff, block_q=32,
                          block_k=32, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_xla_vs_ref(case):
    B, S, T, H, KV, D, causal, window, softcap = case
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, T, KV, D), jnp.float32)
    v = rand(ks[2], (B, T, KV, D), jnp.float32)
    qoff = T - S if causal else 0
    ref = mha_ref(q, k, v, causal=causal, window=window, softcap=softcap,
                  q_offset=qoff)
    out = _mha_xla(q, k, v, causal=causal, window=window, softcap=softcap,
                   scale=None, q_offset=qoff, q_chunk=32, kv_chunk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ATTN_CASES[:3])
def test_flash_attention_grads_vs_ref(case):
    B, S, T, H, KV, D, causal, window, softcap = case
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, T, KV, D), jnp.float32)
    v = rand(ks[2], (B, T, KV, D), jnp.float32)
    dout = rand(ks[3], (B, S, H, D), jnp.float32)
    qoff = T - S if causal else 0

    def loss_x(q, k, v):
        return (_mha_xla(q, k, v, causal=causal, window=window,
                         softcap=softcap, scale=None, q_offset=qoff,
                         q_chunk=32, kv_chunk=32) * dout).sum()

    def loss_r(q, k, v):
        return (mha_ref(q, k, v, causal=causal, window=window,
                        softcap=softcap, q_offset=qoff) * dout).sum()

    gx = jax.grad(loss_x, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gx, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


# the Pallas VJP: ATTN_CASES in 32-row blocks (S=100: the block does not
# divide S), and one case in the default blocks (512 rows: S=600 padded)
PALLAS_GRAD_CASES = [(c, 32) for c in ATTN_CASES] + [
    ((1, 600, 600, 4, 2, 32, True, 0, 0.0), None)]


def _pallas_case(case, block):
    B, S, T, H, KV, D, causal, window, softcap = case
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, T, KV, D), jnp.float32)
    v = rand(ks[2], (B, T, KV, D), jnp.float32)
    dout = rand(ks[3], (B, S, H, D), jnp.float32)
    kw = dict(causal=causal, window=window, softcap=softcap,
              q_offset=T - S if causal else 0)
    return (q, k, v, dout), kw, dict(block_q=block, block_k=block,
                                     interpret=True)


@pytest.mark.parametrize("case,block", PALLAS_GRAD_CASES)
def test_flash_attention_pallas_grads_vs_ref(case, block):
    (q, k, v, dout), kw, pk = _pallas_case(case, block)

    def loss_p(q, k, v):
        return (flash_attention(q, k, v, **kw, **pk) * dout).sum()

    def loss_r(q, k, v):
        return (mha_ref(q, k, v, **kw) * dout).sum()

    gp = jax.grad(loss_p, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("case,block", PALLAS_GRAD_CASES)
def test_flash_attention_pallas_lse_vs_ref(case, block):
    """The residual the backward reads: lse = log sum_k exp(s), f32."""
    (q, k, v, _), kw, pk = _pallas_case(case, block)
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    out, lse = flash_attention_fwd(q, k, v, with_lse=True, **kw, **pk)
    s = jnp.einsum("bsngd,btnd->bngst", q.reshape(B, S, KV, H // KV, D),
                   k) * D ** -0.5
    if kw["softcap"] > 0.0:
        s = jnp.tanh(s / kw["softcap"]) * kw["softcap"]
    qpos = jnp.arange(S)[:, None] + kw["q_offset"]
    kpos = jnp.arange(T)[None, :]
    mask = kpos < T
    if kw["causal"]:
        mask &= kpos <= qpos
    if kw["window"]:
        mask &= kpos > qpos - kw["window"]
    ref = jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
    assert lse.shape == (B, H, 1, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                               np.asarray(ref.reshape(B, H, S)),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(mha_ref(q, k, v, **kw)),
                               atol=2e-5, rtol=2e-5)


DECODE_CASES = [
    (2, 256, 8, 2, 64, 0, 0.0),
    (3, 200, 4, 4, 32, 64, 30.0),
    (2, 512, 16, 8, 128, 0, 0.0),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_flash_decode_pallas_vs_ref(case, dtype):
    B, L, H, KV, D, window, softcap = case
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (B, 1, H, D), dtype)
    kc = rand(ks[1], (B, L, KV, D), dtype)
    vc = rand(ks[2], (B, L, KV, D), dtype)
    lengths = jax.random.randint(ks[3], (B,), L // 2, L + 1)
    ref = flash_decode_ref(q, kc, vc, lengths, window=window, softcap=softcap)
    out = flash_decode_pallas(q, kc, vc, lengths, window=window,
                              softcap=softcap, block_k=64, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_mha_xla_vs_ref(case):
    B, L, H, KV, D, window, softcap = case
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (B, 1, H, D), jnp.float32)
    kc = rand(ks[1], (B, L, KV, D), jnp.float32)
    vc = rand(ks[2], (B, L, KV, D), jnp.float32)
    lengths = jax.random.randint(ks[3], (B,), L // 2, L + 1)
    ref = flash_decode_ref(q, kc, vc, lengths, window=window, softcap=softcap)
    out = decode_mha(q, kc, vc, lengths, window=window, softcap=softcap,
                     kv_chunk=64, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


RWKV_CASES = [(2, 80, 2, 16), (1, 33, 4, 8), (2, 16, 1, 32)]


@pytest.mark.parametrize("shape", RWKV_CASES)
def test_rwkv6_chunked_vs_ref(shape):
    B, S, H, D = shape
    ks = jax.random.split(KEY, 5)
    r = rand(ks[0], (B, S, H, D), jnp.float32) * 0.5
    k = rand(ks[1], (B, S, H, D), jnp.float32) * 0.5
    v = rand(ks[2], (B, S, H, D), jnp.float32) * 0.5
    w = jnp.exp(-jnp.exp(rand(ks[3], (B, S, H, D), jnp.float32) * 0.5))
    u = rand(ks[4], (H, D), jnp.float32) * 0.1
    o1, s1 = _rwkv6_xla(r, k, v, w, u, None, chunk=16)
    o2, s2 = rwkv6_scan_ref(r, k, v, w, u, None)
    # chunked form reassociates exp-cumulations: fp32 roundoff ~1e-3 abs on
    # O(5) outputs (the serial oracle and the chunked path agree to ~3e-4 rel)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=5e-3, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=5e-3, rtol=5e-3)


def test_rwkv6_decode_matches_scan_tail():
    B, S, H, D = 2, 17, 2, 16
    ks = jax.random.split(KEY, 5)
    r = rand(ks[0], (B, S, H, D), jnp.float32) * 0.5
    k = rand(ks[1], (B, S, H, D), jnp.float32) * 0.5
    v = rand(ks[2], (B, S, H, D), jnp.float32) * 0.5
    w = jnp.exp(-jnp.exp(rand(ks[3], (B, S, H, D), jnp.float32) * 0.5))
    u = rand(ks[4], (H, D), jnp.float32) * 0.1
    o_full, s_full = rwkv6_scan_ref(r, k, v, w, u, None)
    _, s_prefix = rwkv6_scan_ref(r[:, :-1], k[:, :-1], v[:, :-1], w[:, :-1],
                                 u, None)
    o_step, s_step = rwkv6_decode_step(r[:, -1], k[:, -1], v[:, -1], w[:, -1],
                                       u, s_prefix)
    np.testing.assert_allclose(np.asarray(o_step), np.asarray(o_full[:, -1]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_step), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)


MAMBA_CASES = [(2, 64, 8, 4), (1, 33, 16, 2), (2, 16, 4, 8)]


@pytest.mark.parametrize("shape", MAMBA_CASES)
def test_mamba_chunked_vs_ref(shape):
    Bt, S, DI, N = shape
    ks = jax.random.split(KEY, 6)
    x = rand(ks[0], (Bt, S, DI), jnp.float32) * 0.5
    dt = jax.nn.softplus(rand(ks[1], (Bt, S, DI), jnp.float32))
    A = -jnp.exp(rand(ks[2], (DI, N), jnp.float32) * 0.3)
    B = rand(ks[3], (Bt, S, N), jnp.float32) * 0.5
    C = rand(ks[4], (Bt, S, N), jnp.float32) * 0.5
    D = jnp.ones((DI,))
    y1, h1 = _mamba_xla(x, dt, A, B, C, D, None, chunk=16)
    y2, h2 = mamba_scan_ref(x, dt, A, B, C, D, None)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-5, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=5e-5, rtol=5e-4)


def test_mamba_decode_matches_scan_tail():
    Bt, S, DI, N = 2, 9, 8, 4
    ks = jax.random.split(KEY, 6)
    x = rand(ks[0], (Bt, S, DI), jnp.float32) * 0.5
    dt = jax.nn.softplus(rand(ks[1], (Bt, S, DI), jnp.float32))
    A = -jnp.exp(rand(ks[2], (DI, N), jnp.float32) * 0.3)
    B = rand(ks[3], (Bt, S, N), jnp.float32) * 0.5
    C = rand(ks[4], (Bt, S, N), jnp.float32) * 0.5
    D = jnp.ones((DI,))
    y_full, h_full = mamba_scan_ref(x, dt, A, B, C, D, None)
    _, h_prefix = mamba_scan_ref(x[:, :-1], dt[:, :-1], A, B[:, :-1],
                                 C[:, :-1], D, None)
    y_step, h_step = mamba_decode_step(x[:, -1], dt[:, -1], A, B[:, -1],
                                       C[:, -1], D, h_prefix)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full[:, -1]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_step), np.asarray(h_full),
                               atol=1e-4, rtol=1e-4)
