"""Jit'd dispatch wrappers over the Pallas kernels.

``use_pallas`` resolution:
  * explicit argument wins;
  * else the compiled kernel runs when the default backend is TPU, and the
    pure-jnp reference runs on every other backend.
No wrapper interprets a kernel: interpret mode is for the kernel tests,
which pass ``interpret=True`` to a kernel module themselves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as REF
from repro.kernels.dist_ce import dist_ce as _dist_ce_kernel
from repro.kernels.emb_dist import emb_dist as _emb_dist_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.ssd_scan import ssd_scan as _ssd_kernel


def _default_use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def dist_ce(student_logits, teacher_logits, use_pallas: bool | None = None):
    """Fused distillation CE + confidences. Returns (ce, t_conf, s_conf)."""
    use = _default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return _dist_ce_kernel(student_logits, teacher_logits)
    return REF.dist_ce_ref(student_logits, teacher_logits)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_pallas: bool | None = None):
    use = _default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return _flash_kernel(q, k, v, causal=causal, window=window)
    return REF.flash_attention_ref(q, k, v, causal=causal, window=window)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128,
             use_pallas: bool | None = None):
    use = _default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return _ssd_kernel(x, dt, A, B, C, D, chunk=chunk)
    from repro.models.ssm import ssd_chunked

    return ssd_chunked(x, dt, A, B, C, D, chunk_size=chunk)


def moe_gmm(lhs, rhs, group_sizes):
    """Grouped matmul over the experts a device holds: each group of
    ``lhs`` rows times its expert's matrix of ``rhs``, float32 out
    (`kernels/moe_gmm`; `ref.moe_gmm_ref` off a TPU). Rows past the last
    group are the caller's to mask."""
    if _default_use_pallas():
        from repro.kernels.moe_gmm import gmm

        return gmm(lhs, rhs, group_sizes)
    return REF.moe_gmm_ref(lhs, rhs, group_sizes)


def topk_wire(logits, k: int = 32, use_pallas: bool | None = None):
    """MHD exchange wire format: (top-k vals, idx, logsumexp)."""
    use = _default_use_pallas() if use_pallas is None else use_pallas
    if use:
        from repro.kernels.topk_wire import topk_wire as _kernel

        return _kernel(logits, k)
    return REF.topk_wire_ref(logits, k)


@functools.partial(
    jax.jit,
    static_argnames=("k", "val_dtype", "idx_dtype", "emb_int8", "use"))
def _topk_wire_frame_jit(heads, emb, d127, *, k: int, val_dtype, idx_dtype,
                         emb_int8: bool, use: bool):
    W, H, B, C = heads.shape
    flat = heads.astype(jnp.float32).reshape(W * H * B, C)
    if use:
        from repro.kernels.topk_wire import topk_wire as _kernel

        vals, idx, lse = _kernel(flat, k)
    else:
        vals, idx, lse = REF.topk_wire_ref(flat, k)
    wire_vals = vals.reshape(W, H, B, k).astype(val_dtype)
    arrays = {
        "vals": wire_vals,
        "idx": idx.reshape(W, H, B, k).astype(idx_dtype),
        "lse": lse.reshape(W, H, B).astype(jnp.float32),
    }
    # finiteness of the inputs AND the wire cast (a finite f32 logit
    # beyond ±65504 overflows to inf in f16) — the host raises
    # NonFiniteError when this flag comes back false
    finite = jnp.all(jnp.isfinite(heads)) & \
        jnp.all(jnp.isfinite(wire_vals.astype(jnp.float32)))
    if emb is not None:
        emb32 = emb.astype(jnp.float32)
        finite = finite & jnp.all(jnp.isfinite(emb32))
        if emb_int8:
            # bit-for-bit twin of wire.quantize_emb_int8: np.rint and
            # jnp.round both round half-to-even, and dividing by the
            # *traced* d127 (not the literal 127.0) forces XLA to emit a
            # true IEEE division — a constant divisor gets rewritten to
            # multiply-by-reciprocal, 1 ulp off numpy's quotient
            amax = jnp.max(jnp.abs(emb32), axis=-1)
            scale = (amax / d127 + 1e-30).astype(jnp.float32)
            arrays["emb_q"] = jnp.clip(
                jnp.round(emb32 / scale[..., None]),
                -127, 127).astype(jnp.int8)
            arrays["emb_scale"] = scale
        else:
            arrays["embedding"] = emb32
    return arrays, finite


def topk_wire_frame(heads, emb, k: int, *, val_dtype: str = "float16",
                    idx_dtype: str = "uint16", emb_encoding: str = "int8",
                    use_pallas: bool | None = None):
    """Fused wire-frame encode: one jitted graph from stacked head logits
    (W, H, B, C) straight to wire-dtype arrays — top-k select, f16 value
    cast, u16/u32 index narrowing, f32 logsumexp, int8 embedding
    quantization and the codec's finiteness checks all on device. Returns
    (arrays, finite_flag); only the small wire-dtype arrays ever cross to
    the host, replacing the dense f32 round-trip through the python
    serializer hop. ``emb=None`` skips the embedding lane."""
    use = _default_use_pallas() if use_pallas is None else use_pallas
    return _topk_wire_frame_jit(
        heads, emb, jnp.float32(127.0), k=k,
        val_dtype=jnp.float16 if val_dtype == "float16" else jnp.float32,
        idx_dtype=jnp.uint16 if idx_dtype == "uint16" else jnp.uint32,
        emb_int8=(emb_encoding == "int8"), use=use)


@functools.partial(
    jax.jit,
    static_argnames=("k", "k_min", "budget_bytes_per_token", "entry_bytes",
                     "val_dtype", "idx_dtype", "emb_int8", "use"))
def _adaptive_topk_wire_frame_jit(heads, emb, d127, *, k: int, k_min: int,
                                  budget_bytes_per_token: int,
                                  entry_bytes: int, val_dtype, idx_dtype,
                                  emb_int8: bool, use: bool):
    W, H, B, C = heads.shape
    flat = heads.astype(jnp.float32).reshape(W * H * B, C)
    if use:
        from repro.kernels.topk_wire import topk_wire as _kernel

        vals, idx, lse = _kernel(flat, k)
    else:
        vals, idx, lse = REF.topk_wire_ref(flat, k)
    wire_vals = vals.reshape(W, H, B, k).astype(val_dtype)
    lse3 = lse.reshape(W, H, B).astype(jnp.float32)

    # per-token entropy of the *main* head's distribution: the signal the
    # byte budget is spent against. H(p) = lse - sum(softmax(x) * x), all
    # f32 — both codec paths run this same jitted graph, so the
    # allocation is bitwise-shared by construction.
    main = heads[:, 0].astype(jnp.float32)  # (W, B, C)
    xs = main - lse3[:, 0][..., None]
    ent = -jnp.sum(jnp.exp(xs) * xs, axis=-1)  # (W, B), nats

    # integer budget: total retained (val, idx) entries across the window,
    # shared across a token's H heads. Static python arithmetic — the
    # budget is a compile-time constant of the frame shape.
    N = W * B
    K_total = (budget_bytes_per_token * N) // (H * entry_bytes)
    R = max(K_total - N * k_min, 0)
    ent_flat = jnp.clip(ent.reshape(N), 0.0, None)
    if R == 0:
        # budget exhausted (or exactly the floor): every token still gets
        # k_min — never less than the top-1 prediction
        k_tok = jnp.full((N,), k_min, jnp.int32)
    else:
        s = jnp.sum(ent_flat)
        w = jnp.where(s > 0, ent_flat, jnp.ones_like(ent_flat))
        sw = jnp.where(s > 0, s, jnp.float32(N))
        quota_f = jnp.float32(R) * w / sw
        quota = jnp.floor(quota_f).astype(jnp.int32)
        # leftover entries go one-each to the largest fractional parts
        # (stable argsort: ties break by token order, deterministically)
        rem = jnp.maximum(jnp.int32(R) - jnp.sum(quota), 0)
        order = jnp.argsort(-(quota_f - jnp.floor(quota_f)))
        rank = jnp.zeros((N,), jnp.int32).at[order].set(
            jnp.arange(N, dtype=jnp.int32))
        bonus = (rank < rem).astype(jnp.int32)
        # clip to [k_min, k]: surplus beyond k is left unspent, so
        # sum(k_tok) <= K_total holds by construction
        k_tok = jnp.clip(k_min + quota + bonus, k_min, k)
    arrays = {
        "vals": wire_vals,
        "idx": idx.reshape(W, H, B, k).astype(idx_dtype),
        "lse": lse3,
        "k_per_token": k_tok.reshape(W, B).astype(jnp.uint16),
    }
    # finiteness of the inputs AND the wire cast, over the full k-rectangle
    # (entries beyond a token's k_tok never travel, but they are the same
    # logits — a non-finite teacher is rejected wholesale, like the fixed
    # codecs)
    finite = jnp.all(jnp.isfinite(heads)) & \
        jnp.all(jnp.isfinite(wire_vals.astype(jnp.float32)))
    if emb is not None:
        emb32 = emb.astype(jnp.float32)
        finite = finite & jnp.all(jnp.isfinite(emb32))
        if emb_int8:
            amax = jnp.max(jnp.abs(emb32), axis=-1)
            scale = (amax / d127 + 1e-30).astype(jnp.float32)
            arrays["emb_q"] = jnp.clip(
                jnp.round(emb32 / scale[..., None]),
                -127, 127).astype(jnp.int8)
            arrays["emb_scale"] = scale
        else:
            arrays["embedding"] = emb32
    return arrays, finite


def adaptive_topk_wire_frame(heads, emb, k: int, *, k_min: int = 1,
                             budget_bytes_per_token: int = 0,
                             entry_bytes: int = 6,
                             val_dtype: str = "float16",
                             idx_dtype: str = "uint16",
                             emb_encoding: str = "int8",
                             use_pallas: bool | None = None):
    """Entropy-adaptive wire-frame encode (`repro.lm.adaptive_wire`).

    One jitted graph from stacked head logits (W, H, B, C) to a
    *rectangular* top-k frame at the codec's k ceiling plus the per-token
    retention plan: top-k select (the same `topk_wire` kernel as the
    fixed codec), main-head entropy, and the integer byte-budget
    allocation ``k_per_token`` (W, B) — how many of the k entries each
    token actually puts on the wire, entropy-weighted under
    ``budget_bytes_per_token`` with a ``k_min`` floor. The host-side
    ragged gather that drops the unspent tail is plain numpy shared by
    the codec's numpy and device paths, so both are byte-identical by
    construction. Returns (arrays, finite_flag)."""
    use = _default_use_pallas() if use_pallas is None else use_pallas
    return _adaptive_topk_wire_frame_jit(
        heads, emb, jnp.float32(127.0), k=k, k_min=k_min,
        budget_bytes_per_token=budget_bytes_per_token,
        entry_bytes=entry_bytes,
        val_dtype=jnp.float16 if val_dtype == "float16" else jnp.float32,
        idx_dtype=jnp.uint16 if idx_dtype == "uint16" else jnp.uint32,
        emb_int8=(emb_encoding == "int8"), use=use)


def emb_dist(student_emb, teacher_emb, use_pallas: bool | None = None):
    use = _default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return _emb_dist_kernel(student_emb, teacher_emb)
    return REF.emb_dist_ref(student_emb, teacher_emb)
