"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as REF
from repro.kernels.dist_ce import dist_ce
from repro.kernels.emb_dist import emb_dist
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssd_scan import ssd_scan


@pytest.mark.parametrize("B,V", [(8, 512), (37, 1000), (64, 2048), (3, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dist_ce_sweep(B, V, dtype):
    s = (jax.random.normal(jax.random.PRNGKey(0), (B, V)) * 3).astype(dtype)
    t = (jax.random.normal(jax.random.PRNGKey(1), (B, V)) * 3).astype(dtype)
    ce, tc, sc = dist_ce(s, t, interpret=True, block_rows=16, block_v=128)
    ce_r, tc_r, sc_r = REF.dist_ce_ref(s, t)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(ce, ce_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(tc, tc_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(sc, sc_r, rtol=tol, atol=tol)


@pytest.mark.parametrize("B,T,H,KV,d,causal,window", [
    (2, 64, 4, 2, 32, True, 0),
    (1, 100, 2, 2, 16, True, 24),
    (2, 32, 4, 4, 64, False, 0),
    (1, 256, 8, 2, 32, True, 64),
    (1, 48, 4, 1, 16, True, 0),
])
def test_flash_attention_sweep(B, T, H, KV, d, causal, window):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, KV, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, KV, d))
    o = flash_attention(q, k, v, causal=causal, window=window,
                        block_t=32, block_s=32, interpret=True)
    r = REF.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 2, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 32)).astype(jnp.bfloat16)
    o = flash_attention(q, k, v, block_t=32, block_s=32, interpret=True)
    r = REF.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=3e-2)


@pytest.mark.parametrize("Bt,T,H,P,N,chunk", [
    (2, 64, 4, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 32, 3, 8, 4, 8),
    (1, 64, 1, 64, 32, 64),
])
def test_ssd_scan_sweep(Bt, T, H, P, N, chunk):
    x = jax.random.normal(jax.random.PRNGKey(0), (Bt, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (Bt, T, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)))
    B = jax.random.normal(jax.random.PRNGKey(3), (Bt, T, N))
    C = jax.random.normal(jax.random.PRNGKey(4), (Bt, T, N))
    D = jnp.ones((H,))
    y, st = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    y_r, st_r = REF.ssd_scan_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_r),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("B,E", [(16, 64), (37, 128), (5, 512)])
def test_emb_dist_sweep(B, E):
    s = jax.random.normal(jax.random.PRNGKey(0), (B, E))
    t = jax.random.normal(jax.random.PRNGKey(1), (B, E))
    o = emb_dist(s, t, interpret=True, block_rows=16)
    r = REF.emb_dist_ref(s, t)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sizes,k,n", [
    ((5, 0, 130, 27), 128, 256),   # an empty group, rows past the groups
    ((200, 56), 256, 200),         # n and the rows not a tile multiple
])
def test_moe_gmm_matches_ragged_dot(sizes, k, n):
    """The grouped matmul kernels (forward, input and weight gradients)
    in interpret mode against `jax.lax.ragged_dot`, float32 throughout;
    rows past the last group are the caller's to mask."""
    m = 300
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n))
    gs = jnp.asarray(sizes, jnp.int32)
    rows = sum(sizes)
    ct = jax.random.normal(jax.random.PRNGKey(2), (m, n))
    mask = (jnp.arange(m) < rows)[:, None]

    def loss(fn, a, b):
        return jnp.sum(jnp.where(mask, fn(a, b), 0.0) * ct)

    kernel = lambda a, b: gmm(a, b, gs, compute_dtype=jnp.float32,  # noqa
                              interpret=True)
    oracle = lambda a, b: REF.moe_gmm_ref(a, b, gs)  # noqa: E731
    out, ref = kernel(lhs, rhs), oracle(lhs, rhs)
    np.testing.assert_allclose(np.asarray(out[:rows]), np.asarray(ref[:rows]),
                               rtol=1e-4, atol=1e-3)
    g = jax.grad(lambda a, b: loss(kernel, a, b), argnums=(0, 1))(lhs, rhs)
    g_ref = jax.grad(lambda a, b: loss(oracle, a, b), argnums=(0, 1))(lhs,
                                                                      rhs)
    np.testing.assert_allclose(np.asarray(g[0][:rows]),
                               np.asarray(g_ref[0][:rows]),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(g_ref[1]),
                               rtol=1e-4, atol=1e-2)


def test_moe_gmm_in_bfloat16_is_within_rounding():
    """The TPU operand type: bfloat16 products, float32 accumulation."""
    lhs = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128))
    gs = jnp.asarray([100, 120], jnp.int32)
    out = gmm(lhs, rhs, gs, interpret=True)[:220]
    ref = REF.moe_gmm_ref(lhs, rhs, gs)[:220]
    assert out.dtype == jnp.float32
    err = np.abs(np.asarray(out) - np.asarray(ref)).max()
    assert err < 0.02 * np.abs(np.asarray(ref)).max()


def test_ops_dispatch_cpu_uses_ref():
    from repro.kernels import ops
    s = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    t = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    ce, tc, sc = ops.dist_ce(s, t)  # CPU -> ref path
    ce_r, _, _ = REF.dist_ce_ref(s, t)
    np.testing.assert_allclose(ce, ce_r, rtol=1e-6)
