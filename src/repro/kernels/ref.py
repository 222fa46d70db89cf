"""Pure-jnp oracles for every Pallas kernel.

These are the *definitions of correctness*: kernel tests sweep shapes/dtypes
and assert_allclose against these functions. They are also the CPU execution
path of ops.py (the kernels are TPU-targeted; interpret=True validates the
kernel bodies themselves on CPU).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# distillation cross-entropy (the MHD hot spot for 262k vocabs)
# ---------------------------------------------------------------------------

def dist_ce_ref(student_logits, teacher_logits):
    """Per-row distillation CE + confidences.

    student_logits, teacher_logits: (B, V) float.
    Returns (ce (B,), teacher_conf (B,), student_conf (B,)):
        ce_b     = -Σ_v softmax(t)_v · log softmax(s)_v
        *_conf_b = max_v softmax(·)_v      (Λ of Eq. 4)
    """
    t = teacher_logits.astype(jnp.float32)
    s = student_logits.astype(jnp.float32)
    p_t = jax.nn.softmax(t, axis=-1)
    logp_s = jax.nn.log_softmax(s, axis=-1)
    ce = -jnp.sum(p_t * logp_s, axis=-1)
    t_conf = jnp.max(p_t, axis=-1)
    s_conf = jnp.max(jax.nn.softmax(s, axis=-1), axis=-1)
    return ce, t_conf, s_conf


# ---------------------------------------------------------------------------
# flash attention (causal / sliding window, GQA)
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B, T, H, d); k, v: (B, S, KV, d); GQA via head grouping.

    window > 0 restricts key j to (i - window, i] (sliding window attention).
    Returns (B, T, H, d).
    """
    B, T, H, d = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    qpos = jnp.arange(T)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, T, H, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunked scan
# ---------------------------------------------------------------------------

def ssd_scan_ref(x, dt, A, B, C, D):
    """Sequential SSD recurrence (same math as models/ssm.ssd_reference).

    x: (Bt, T, H, P); dt: (Bt, T, H); A: (H,); B, C: (Bt, T, N); D: (H,).
    Returns (y (Bt, T, H, P), final_state (Bt, H, P, N)).
    """
    from repro.models.ssm import ssd_reference

    return ssd_reference(x, dt, A, B, C, D)


# ---------------------------------------------------------------------------
# top-k wire-format packing (MHD exchange)
# ---------------------------------------------------------------------------

def topk_wire_ref(logits, k: int = 32):
    """(B, V) -> (vals (B,k) f32, idx (B,k) i32, lse (B,) f32)."""
    x = logits.astype(jnp.float32)
    vals, idx = jax.lax.top_k(x, k)
    lse = jax.nn.logsumexp(x, axis=-1)
    return vals, idx.astype(jnp.int32), lse


# ---------------------------------------------------------------------------
# normalized embedding distillation (Eq. 2)
# ---------------------------------------------------------------------------

def emb_dist_ref(student_emb, teacher_emb, eps: float = 1e-8):
    """Per-row squared distance of L2-normalized embeddings. (B, E) -> (B,)."""
    s = student_emb.astype(jnp.float32)
    t = teacher_emb.astype(jnp.float32)
    s = s / (jnp.linalg.norm(s, axis=-1, keepdims=True) + eps)
    t = t / (jnp.linalg.norm(t, axis=-1, keepdims=True) + eps)
    return jnp.sum(jnp.square(s - t), axis=-1)


# ---------------------------------------------------------------------------
# grouped matmul of the experts a device holds
# ---------------------------------------------------------------------------

def moe_gmm_ref(lhs, rhs, group_sizes):
    """Each group of ``lhs`` rows (m, k) times its matrix of ``rhs``
    (groups, k, n), float32; rows past the last group give zeros."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)
