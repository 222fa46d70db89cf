"""Mamba2 SSD: chunked vs sequential reference, decode-step consistency."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import MambaConfig
from repro.models.ssm import (
    init_mamba2,
    init_mamba2_cache,
    mamba2_apply,
    mamba2_decode,
    ssd_chunked,
    ssd_reference,
)


def _rand_ssd(Bt=2, T=64, H=4, P=16, N=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, T, N))
    C = jax.random.normal(ks[4], (Bt, T, N))
    D = jnp.ones((H,))
    return x, dt, A, B, C, D


def test_chunked_matches_sequential():
    args = _rand_ssd()
    y_ref, h_ref = ssd_reference(*args)
    for chunk in (8, 16, 32, 64):
        y, h = ssd_chunked(*args, chunk_size=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=3e-4, atol=3e-4)


def test_chunked_gradients_finite():
    args = _rand_ssd(T=32)

    def loss(x):
        y, _ = ssd_chunked(x, *args[1:], chunk_size=8)
        return jnp.sum(jnp.square(y))

    g = jax.grad(loss)(args[0])
    assert np.all(np.isfinite(np.asarray(g)))


def test_mamba2_decode_matches_full_forward():
    cfg = MambaConfig(d_state=8, d_conv=4, expand=2, head_dim=8, chunk_size=8)
    D_model = 16
    params = init_mamba2(jax.random.PRNGKey(0), D_model, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, D_model))
    full = mamba2_apply(params, x, cfg, use_chunked=True)
    cache = init_mamba2_cache(2, D_model, cfg)
    outs = []
    for t in range(16):
        y, cache = mamba2_decode(params, x[:, t:t + 1], cache, cfg)
        outs.append(y)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_state_decay_bounded():
    """With A<0 and bounded inputs the SSD state stays bounded (stability)."""
    x, dt, A, B, C, D = _rand_ssd(T=128)
    _, h = ssd_reference(x, dt, A, B, C, D)
    assert np.all(np.isfinite(np.asarray(h)))
    assert np.abs(np.asarray(h)).max() < 1e4


def _rand_grouped(Bt=2, T=32, H=16, P=8, G=8, N=8, seed=3):
    x, dt, A, _, _, D = _rand_ssd(Bt, T, H, P, N, seed)
    kb, kc = jax.random.split(jax.random.PRNGKey(seed + 1))
    B = jax.random.normal(kb, (Bt, T, G, N))
    C = jax.random.normal(kc, (Bt, T, G, N))
    return x, dt, A, B, C, D


def test_grouped_ssd_matches_per_head_sequential_scans():
    """With 8 groups, head h reads group h // (H / G): each head's output
    and state are those of a one-head scan over its group's B and C."""
    x, dt, A, B, C, D = _rand_grouped()
    H, G = x.shape[2], B.shape[2]
    y_ref = []
    h_ref = []
    for h in range(H):
        g = h // (H // G)
        y, s = ssd_reference(x[:, :, h:h + 1], dt[:, :, h:h + 1], A[h:h + 1],
                             B[:, :, g], C[:, :, g], D[h:h + 1])
        y_ref.append(y)
        h_ref.append(s)
    y_ref = jnp.concatenate(y_ref, axis=2)
    h_ref = jnp.concatenate(h_ref, axis=1)
    for ssd, kw in ((ssd_reference, {}), (ssd_chunked, {"chunk_size": 8})):
        y, h = ssd(x, dt, A, B, C, D, **kw)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=3e-4, atol=3e-4)


def _mamba2_apply_one_group(params, x, cfg):
    """The single-group forward as it was written before groups: B and C
    as (Bt, T, N), the gated norm over all of d_inner."""
    from repro.models.layers import causal_conv1d_apply, norm_apply
    from repro.models.ssm import _split_in_proj

    B_, T, D_model = x.shape
    d_in, H, N = cfg.d_inner(D_model), cfg.num_heads(D_model), cfg.d_state
    zxd = jnp.einsum("...d,de->...e", x, params["in_proj"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    z, xbc, dt_raw = _split_in_proj(zxd, d_in, N, H)
    xbc = jax.nn.silu(causal_conv1d_apply(params["conv"], xbc))
    xc = xbc[..., :d_in].reshape(B_, T, H, cfg.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    y, _ = ssd_chunked(xc, dt, -jnp.exp(params["A_log"]),
                       xbc[..., d_in:d_in + N], xbc[..., d_in + N:],
                       params["D"], chunk_size=cfg.chunk_size)
    y = y.reshape(B_, T, d_in)
    y = norm_apply(params["norm"], y * jax.nn.silu(
        z.astype(jnp.float32)).astype(y.dtype))
    return jnp.einsum("...e,ed->...d", y, params["out_proj"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def test_one_group_mamba2_is_bitwise_the_ungrouped_forward():
    cfg = MambaConfig(d_state=8, d_conv=4, expand=2, head_dim=8, chunk_size=8)
    params = init_mamba2(jax.random.PRNGKey(0), 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    new = jax.jit(lambda p, x: mamba2_apply(p, x, cfg))(params, x)
    old = jax.jit(lambda p, x: _mamba2_apply_one_group(p, x, cfg))(params, x)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_grouped_gated_norm_normalises_each_group():
    from repro.models.ssm import gated_norm

    y = jax.random.normal(jax.random.PRNGKey(0), (3, 16)) * 4
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    scale = jnp.linspace(0.5, 2.0, 16)
    out = np.asarray(gated_norm({"scale": scale}, y, z, groups=4, eps=1e-5))
    g = np.asarray(y * jax.nn.silu(z)).reshape(3, 4, 4)
    want = g / np.sqrt(np.mean(g ** 2, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(out, want.reshape(3, 16) * np.asarray(scale),
                               rtol=1e-5, atol=1e-6)


def test_grouped_mamba2_decode_matches_full_forward():
    """Explicit heads (d_inner = heads x head_dim, not expand x d_model)
    and two groups of B and C: token-by-token decode reproduces the
    chunked forward."""
    cfg = MambaConfig(d_state=8, d_conv=4, head_dim=4, chunk_size=8,
                      n_groups=2, n_heads=6)
    D_model = 16
    params = init_mamba2(jax.random.PRNGKey(0), D_model, cfg)
    assert params["out_proj"].shape == (24, D_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, D_model))
    full = mamba2_apply(params, x, cfg, eps=1e-5)
    cache = init_mamba2_cache(2, D_model, cfg)
    outs = []
    for t in range(16):
        y, cache = mamba2_decode(params, x[:, t:t + 1], cache, cfg, eps=1e-5)
        outs.append(y)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)
