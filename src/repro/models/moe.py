"""Token-choice Mixture-of-Experts with no dropped pairs, over the
experts this device holds.

Design:
  * router in fp32 at HIGHEST precision over *all* ``num_experts``;
    top-k of a softmax, or of sigmoid scores (DeepSeek-v3, Nemotron-H),
    where a correction bias may steer the choice and the chosen weights
    are renormalised and scaled by ``routed_scaling``;
  * expert parallelism: the layer holds experts
    ``[expert_offset, expert_offset + experts_held)`` and computes only
    their part of the result; what the absent experts would add is left
    to the devices that hold them (on one device, no exchange). Such a
    share computes no gradient through the routing weights, which would
    need the absent experts' outputs;
  * dispatch: every (token, choice) pair routed to a held expert is kept,
    none dropped: pairs are sorted by expert, and a grouped matmul
    (`kernels.ops.moe_gmm`) multiplies each expert's rows by its own
    weights; rows of pairs routed elsewhere sort last and are masked;
  * experts: SwiGLU (``act="silu"``) or an ungated relu² MLP
    (``relu2``), and an optional shared expert of its own width;
  * aux load-balance loss (Switch-style): E * Σ_e f_e · P_e.

The explicit all-to-all expert-parallel variant (shard_map) lives in
``moe_a2a.py``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as K
from repro.models.config import MoEConfig
from repro.models.layers import dense_init, init_mlp, mlp_apply


def init_moe(key, d_model: int, cfg: MoEConfig, act: str = "silu",
             dtype=jnp.float32):
    k = jax.random.split(key, 6)
    E, F = cfg.held, cfg.d_ff_expert
    std = 1.0 / math.sqrt(d_model)
    params = {
        "router": dense_init(k[0], d_model, cfg.num_experts, jnp.float32),
        "w_up": (jax.random.normal(k[2], (E, d_model, F)) * std).astype(dtype),
        "w_down": (jax.random.normal(k[3], (E, F, d_model)) / math.sqrt(F)).astype(dtype),
    }
    if act == "silu":  # gated (SwiGLU) experts
        params["w_gate"] = (jax.random.normal(k[1], (E, d_model, F))
                            * std).astype(dtype)
    if cfg.router_bias:  # a buffer the gradient does not train
        params["router_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    if cfg.shared_width:
        params["shared"] = init_mlp(k[4], d_model, cfg.shared_width, act=act,
                                    dtype=dtype)
    return params


def router_topk(logits, top_k: int, scoring: str = "softmax", bias=None,
                scaling: float = 1.0):
    """Return (weights (N,k), ids (N,k), probs (N,E)).

    softmax: the top-k probabilities. sigmoid: the top-k of the scores
    plus ``bias`` (the choice only), their scores renormalised to sum to
    1, then all weights times ``scaling``."""
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + bias
        _, ids = jax.lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        probs = scores / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-20)
    else:
        raise ValueError(scoring)
    return weights * scaling, ids, probs


def load_balance_loss(probs, ids, num_experts: int) -> jnp.ndarray:
    """Switch-Transformer aux loss: E · Σ_e f_e P_e (top-1 dispatch fraction)."""
    top1 = ids[..., 0]
    f = jnp.mean(jax.nn.one_hot(top1, num_experts, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def _experts(params, xs, group_sizes, act: str):
    up = K.moe_gmm(xs, params["w_up"], group_sizes)
    if act == "silu":
        h = jax.nn.silu(K.moe_gmm(xs, params["w_gate"], group_sizes)) * up
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(act)
    return K.moe_gmm(h.astype(xs.dtype), params["w_down"], group_sizes)


def moe_apply(
    params,
    x,  # (B, T, D) or (N, D)
    cfg: MoEConfig,
    act: str = "silu",
    scoring: str = "softmax",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (output matching x's shape, aux_loss scalar, stats), where
    stats = [rows routed to the held experts, rows of the busiest held
    expert] (float32)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    E, Kc, Eh = cfg.num_experts, cfg.top_k, cfg.held

    with jax.named_scope("moe/route"):
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        bias = params.get("router_bias")
        weights, ids, probs = router_topk(
            logits, Kc, scoring,
            None if bias is None else jax.lax.stop_gradient(bias),
            cfg.routed_scaling)
        if Eh < E:
            # the combine weights' gradient needs every chosen expert's
            # output; without the absent ones it trains the routing
            # toward the held experts, which the whole layer never does
            weights = jax.lax.stop_gradient(weights)
        aux = load_balance_loss(probs, ids, E) * cfg.router_aux_weight
        # pairs sorted by held expert; pairs routed elsewhere sort last
        local = ids.reshape(-1) - cfg.expert_offset  # (N*K,) token-major
        held = (local >= 0) & (local < Eh)
        key = jnp.where(held, local, Eh)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.bincount(key, length=Eh + 1)[:Eh].astype(jnp.int32)
        rows = jnp.sum(group_sizes)
        valid = (jnp.arange(N * Kc) < rows)[:, None]
        token = order // Kc
        xs = jnp.where(valid, xf[token], 0).astype(xf.dtype)

    with jax.named_scope("moe/experts"):
        out = _experts(params, xs, group_sizes, act)

    with jax.named_scope("moe/combine"):
        # rows past the groups were never written: mask before any use
        out = jnp.where(valid, out, 0.0)
        w = weights.reshape(-1)[order].astype(jnp.float32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * Kc, dtype=order.dtype))
        y = (out * w[:, None])[inverse].reshape(N, Kc, D).sum(axis=1)
        y = y.astype(xf.dtype)
        if "shared" in params:
            y = y + mlp_apply(params["shared"], xf, act=act)

    stats = jnp.stack([rows, jnp.max(group_sizes)]).astype(jnp.float32)
    return y.reshape(orig_shape), aux, stats
