"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) block.

TPU adaptation: instead of the CUDA selective-scan, the sequence is
processed in chunks — intra-chunk interactions are a dense (L_c × L_c)
masked matmul (MXU-friendly), inter-chunk state is carried by a
``lax.scan`` over chunks. The Pallas kernel (kernels/ssd_scan.py) fuses the
intra-chunk compute per (chunk, head) tile in VMEM; this module provides the
pure-jnp implementation used on CPU and as the kernel oracle.

Scalar-identities follow the Mamba2 paper: per head h with state N and head
dim P,   h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_tᵀ,   y_t = C_tᵀ h_t + D x_t.
B and C come in ``n_groups`` groups, head h reading group h // (H / G):
one group in the Mamba2 releases, 8 in Nemotron-H. The gated RMSNorm
before the output projection normalises each group's d_inner / G
channels on their own.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.common.sharding import maybe_shard
from repro.models.config import MambaConfig
from repro.models.layers import (
    causal_conv1d_apply,
    causal_conv1d_step,
    dense_init,
    init_causal_conv1d,
    init_norm,
    norm_apply,
)


def init_mamba2(key, d_model: int, cfg: MambaConfig, dtype=jnp.float32):
    k = jax.random.split(key, 6)
    d_in = cfg.d_inner(d_model)
    H = cfg.num_heads(d_model)
    N = cfg.n_groups * cfg.d_state
    conv_ch = d_in + 2 * N  # x, B, C all pass through the causal conv
    # dt_bias init so that softplus(dt_bias) spans ~[1e-3, 1e-1] (mamba2 default)
    u = jax.random.uniform(k[3], (H,))
    dt0 = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))  # inverse softplus
    return {
        "in_proj": dense_init(k[0], d_model, 2 * d_in + 2 * N + H, dtype),
        "conv": init_causal_conv1d(k[1], conv_ch, cfg.d_conv, dtype),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": init_norm(d_in, "rmsnorm", dtype),
        "out_proj": dense_init(k[2], d_in, d_model, dtype),
    }


def _split_in_proj(z_xbc_dt, d_in: int, N: int, H: int):
    z = z_xbc_dt[..., :d_in]
    xbc = z_xbc_dt[..., d_in : 2 * d_in + 2 * N]
    dt = z_xbc_dt[..., 2 * d_in + 2 * N :]
    return z, xbc, dt


def _per_group(ssd_one, x, dt, A, B, C, D, chunk_size):
    """``ssd_one`` (one group of B and C) run once per group, over that
    group's heads. B, C: (Bt, T, N), one group, or (Bt, T, G, N)."""
    if B.ndim == 3:
        return ssd_one(x, dt, A, B, C, D, chunk_size)
    G = B.shape[2]
    if G == 1:
        return ssd_one(x, dt, A, B[:, :, 0], C[:, :, 0], D, chunk_size)
    Bt, T, H, P = x.shape
    hg = H // G
    y, h = jax.vmap(
        lambda x_, dt_, A_, B_, C_, D_: ssd_one(x_, dt_, A_, B_, C_, D_,
                                                chunk_size),
        in_axes=(2, 2, 0, 2, 2, 0), out_axes=(2, 1))(
        x.reshape(Bt, T, G, hg, P), dt.reshape(Bt, T, G, hg),
        A.reshape(G, hg), B, C, D.reshape(G, hg))
    return y.reshape(Bt, T, H, P), h.reshape(Bt, H, P, B.shape[-1])


def ssd_reference(x, dt, A, B, C, D, chunk_size: int = 0):
    """Sequential-scan oracle.

    x: (Bt, T, H, P); dt: (Bt, T, H); A: (H,); B, C: (Bt, T, N) or
    (Bt, T, G, N); D: (H,)
    returns y: (Bt, T, H, P), final_state: (Bt, H, P, N)
    """
    return _per_group(_ssd_reference_one, x, dt, A, B, C, D, chunk_size)


def _ssd_reference_one(x, dt, A, B, C, D, chunk_size: int = 0):
    Bt, T, H, P = x.shape
    N = B.shape[-1]
    decay = jnp.exp(dt * A[None, None, :])  # (Bt, T, H)

    def step(h, inputs):
        x_t, dt_t, dec_t, B_t, C_t = inputs
        # h: (Bt, H, P, N)
        h = h * dec_t[:, :, None, None] + (
            (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :]
        )
        y_t = jnp.einsum("bhpn,bn->bhp", h, C_t)
        return h, y_t

    init = jnp.zeros((Bt, H, P, N), jnp.float32)
    xs = (
        x.astype(jnp.float32).swapaxes(0, 1),
        dt.swapaxes(0, 1),
        decay.swapaxes(0, 1),
        B.astype(jnp.float32).swapaxes(0, 1),
        C.astype(jnp.float32).swapaxes(0, 1),
    )
    h_final, ys = jax.lax.scan(step, init, xs)
    y = ys.swapaxes(0, 1) + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_final


def ssd_chunked(x, dt, A, B, C, D, chunk_size: int = 64):
    """Chunked SSD (training path): O(T·L_c) with MXU-dense intra-chunk
    math. Shapes as `ssd_reference`."""
    return _per_group(_ssd_chunked_one, x, dt, A, B, C, D, chunk_size)


def _ssd_chunked_one(x, dt, A, B, C, D, chunk_size: int = 64):
    Bt, T, H, P = x.shape
    N = B.shape[-1]
    L = chunk_size
    assert T % L == 0, f"seq {T} not divisible by chunk {L}"
    nc = T // L

    xs = x.astype(jnp.float32).reshape(Bt, nc, L, H, P)
    dts = dt.reshape(Bt, nc, L, H)
    Bs = B.astype(jnp.float32).reshape(Bt, nc, L, N)
    Cs = C.astype(jnp.float32).reshape(Bt, nc, L, N)

    a = dts * A[None, None, None, :]  # (Bt, nc, L, H) log-decay increments
    s = jnp.cumsum(a, axis=2)  # inclusive cumulative log decay within chunk
    total = s[:, :, -1, :]  # (Bt, nc, H)

    # intra-chunk: M[t, u] = C_t·B_u · exp(s_t - s_u) · dt_u   for u <= t
    CB = jnp.einsum("bcln,bcmn->bclm", Cs, Bs)  # (Bt, nc, L, L)
    seg = s[:, :, :, None, :] - s[:, :, None, :, :]  # (Bt, nc, L, L, H)
    tri = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, None, :, :, None]
    # mask BEFORE exp: upper-triangle seg is positive and overflows, and
    # grad-through-where of an inf produces NaN
    gate = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    M = CB[..., None] * gate * dts[:, :, None, :, :]  # (Bt,nc,L,L,H)
    y_intra = jnp.einsum("bclmh,bcmhp->bclhp", M, xs)

    # chunk-end states: G = Σ_u exp(total - s_u) dt_u B_u x_uᵀ
    w = jnp.exp(total[:, :, None, :] - s) * dts  # (Bt, nc, L, H)
    G = jnp.einsum("bclh,bcln,bclhp->bchpn", w, Bs, xs)  # (Bt,nc,H,P,N)

    # inter-chunk recurrence over nc chunks
    def step(h, inputs):
        G_c, tot_c = inputs  # (Bt,H,P,N), (Bt,H)
        h_out = h  # state entering this chunk
        h = h * jnp.exp(tot_c)[:, :, None, None] + G_c
        return h, h_out

    init = jnp.zeros((Bt, H, P, N), jnp.float32)
    h_final, h_starts = jax.lax.scan(
        step, init, (G.swapaxes(0, 1), total.swapaxes(0, 1))
    )
    h_starts = h_starts.swapaxes(0, 1)  # (Bt, nc, H, P, N)

    # inter-chunk contribution: y += C_t · (exp(s_t) h_start)
    y_inter = jnp.einsum(
        "bcln,bclh,bchpn->bclhp", Cs, jnp.exp(s), h_starts
    )
    y = (y_intra + y_inter).reshape(Bt, T, H, P)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_final


def gated_norm(params, y, z, groups: int, eps: float = 1e-6):
    """``rmsnorm(y * silu(z))``, each of ``groups`` groups of channels
    normalised on its own, then scaled."""
    g = (y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype))
    if groups == 1:
        return norm_apply(params, g, eps=eps)
    lead, d = g.shape[:-1], g.shape[-1]
    g32 = g.astype(jnp.float32).reshape(lead + (groups, d // groups))
    var = jnp.mean(jnp.square(g32), axis=-1, keepdims=True)
    g32 = (g32 * jax.lax.rsqrt(var + eps)).reshape(lead + (d,))
    return (g32 * params["scale"].astype(jnp.float32)).astype(g.dtype)


def mamba2_apply(params, x, cfg: MambaConfig, *, use_chunked: bool = True,
                 eps: float = 1e-6):
    """Full-sequence forward. x: (B, T, D) -> (B, T, D)."""
    B_, T, D_model = x.shape
    d_in = cfg.d_inner(D_model)
    H = cfg.num_heads(D_model)
    G, N = cfg.n_groups, cfg.d_state

    zxd = jnp.einsum("...d,de->...e", x, params["in_proj"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    z, xbc, dt_raw = _split_in_proj(zxd, d_in, G * N, H)
    xbc = jax.nn.silu(causal_conv1d_apply(params["conv"], xbc))
    xc = xbc[..., :d_in].reshape(B_, T, H, cfg.head_dim)
    Bmat = xbc[..., d_in : d_in + G * N].reshape(B_, T, G, N)
    Cmat = xbc[..., d_in + G * N :].reshape(B_, T, G, N)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    ssd = ssd_chunked if (use_chunked and T % cfg.chunk_size == 0) else ssd_reference
    y, _ = ssd(xc, dt, A, Bmat, Cmat, params["D"],
               chunk_size=cfg.chunk_size)
    y = gated_norm(params["norm"], y.reshape(B_, T, d_in), z, G, eps)
    return jnp.einsum("...e,ed->...d", y, params["out_proj"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def init_mamba2_cache(batch: int, d_model: int, cfg: MambaConfig,
                      dtype=jnp.float32):
    d_in = cfg.d_inner(d_model)
    H = cfg.num_heads(d_model)
    conv_ch = d_in + 2 * cfg.n_groups * cfg.d_state
    return {
        "ssm": jnp.zeros((batch, H, cfg.head_dim, cfg.d_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.d_conv - 1, conv_ch), dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def mamba2_decode(params, x, cache, cfg: MambaConfig, *, eps: float = 1e-6):
    """Single-token step. x: (B, 1, D)."""
    B_, _, D_model = x.shape
    d_in = cfg.d_inner(D_model)
    H = cfg.num_heads(D_model)
    G, N = cfg.n_groups, cfg.d_state

    zxd = jnp.einsum("btd,de->bte", x, params["in_proj"],
                     preferred_element_type=jnp.float32).astype(x.dtype)[:, 0]
    z, xbc, dt_raw = _split_in_proj(zxd, d_in, G * N, H)
    xbc, conv_state = causal_conv1d_step(params["conv"], xbc, cache["conv"])
    xbc = jax.nn.silu(xbc)
    xc = xbc[..., :d_in].reshape(B_, H, cfg.head_dim).astype(jnp.float32)
    # head h reads group h // (H / G)
    Bmat = jnp.repeat(xbc[..., d_in : d_in + G * N].reshape(B_, G, N),
                      H // G, axis=1).astype(jnp.float32)  # (B,H,N)
    Cmat = jnp.repeat(xbc[..., d_in + G * N :].reshape(B_, G, N),
                      H // G, axis=1).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * A[None, :])  # (B,H)

    h = cache["ssm"] * decay[:, :, None, None] + (
        (dt[:, :, None] * xc)[..., None] * Bmat[:, :, None, :]
    )
    y = jnp.einsum("bhpn,bhn->bhp", h, Cmat) + xc * params["D"][None, :, None]
    y = gated_norm(params["norm"], y.reshape(B_, d_in).astype(x.dtype), z,
                   G, eps)
    out = jnp.einsum("be,ed->bd", y, params["out_proj"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    new_cache = {"ssm": h, "conv": conv_state, "index": cache["index"] + 1}
    return out[:, None, :], new_cache
