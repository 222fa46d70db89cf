"""Adapter applying MHD to language-model clients (beyond-paper extension).

For an LM client the MHD "sample" is a *token position* on the public text
pool: the prediction is the next-token distribution, the embedding ξ_i is the
final hidden state at that position. This adapter reshapes LM bundle outputs
into the (B', C) / (m, B', C) layout that core/mhd.py expects, with
B' = batch · (T−1) next-token positions.

Every assigned architecture works through this adapter (the MHD math never
looks inside the backbone).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.zoo import ModelBundle


def lm_mhd_outputs(bundle: ModelBundle, params, batch: Dict[str, Any],
                   max_positions: int = 0,
                   position_seed: Optional[int] = None) -> Dict[str, Any]:
    """Run an LM and flatten to MHD client outputs.

    Returns {"embedding": (B', D), "logits": (B', V), "aux_logits": (m, B', V),
             "labels": (B',), "sample_rows": (B',), "aux_loss": ()} plus a
    MoE model's "moe_stats" (`transformer.apply_lm`), where labels are the next
    tokens (used as the private CE target) and sample_rows maps each
    position back to its source sequence (per-domain eval aggregation).

    ``max_positions`` bounds B'. With ``position_seed=None`` the kept
    positions are the batch-head prefix (the historical behavior — a
    *biased* subset: early positions of early sequences only). With a
    seed they are a fixed random subset: ``permutation(PRNGKey(seed),
    B·(T−1))[:max_positions]``, constant-folded under jit and identical
    for every client/teacher sharing the seed — which a fleet must,
    since distillation aligns teachers and students row-by-row.
    """
    from repro.common.sharding import maybe_shard

    out = bundle.apply(params, batch)
    tokens = batch["tokens"]
    hidden = out["hidden"][:, :-1]  # (B, T-1, D)
    logits = out["logits"][:, :-1].astype(jnp.bfloat16)
    labels = tokens[:, 1:]
    B, Tm1, D = hidden.shape
    V = logits.shape[-1]
    # reshapes that merge a sharded batch dim with time lose their sharding
    # (XLA replicates) — re-constrain the flattened position dim
    emb = maybe_shard(hidden.reshape(B * Tm1, D), "batch", "none")
    lg = maybe_shard(logits.reshape(B * Tm1, V), "batch", "model")
    aux = out["aux_heads"]
    aux_flat = None
    if aux is not None:
        aux_flat = maybe_shard(
            aux[:, :, :-1].astype(jnp.bfloat16).reshape(aux.shape[0],
                                                        B * Tm1, V),
            "none", "batch", "model")
    lab = labels.reshape(B * Tm1)
    rows = jnp.repeat(jnp.arange(B, dtype=jnp.int32), Tm1)
    if max_positions and B * Tm1 > max_positions:
        if position_seed is None:
            emb = emb[:max_positions]
            lg = lg[:max_positions]
            lab = lab[:max_positions]
            rows = rows[:max_positions]
            if aux_flat is not None:
                aux_flat = aux_flat[:, :max_positions]
        else:
            keep = jax.random.permutation(
                jax.random.PRNGKey(position_seed),
                B * Tm1)[:max_positions]
            emb = emb[keep]
            lg = lg[keep]
            lab = lab[keep]
            rows = rows[keep]
            if aux_flat is not None:
                aux_flat = aux_flat[:, keep]
    res = {"embedding": emb, "logits": lg, "aux_logits": aux_flat,
           "labels": lab, "sample_rows": rows, "aux_loss": out["aux_loss"]}
    if "moe_stats" in out:
        res["moe_stats"] = out["moe_stats"]
    return res


def lm_mhd_loss(bundle: ModelBundle, params, private_batch, public_batch,
                teacher_outs, mhd_cfg, rng=None):
    """Eq. (1) for an LM client: private next-token CE + public distillation."""
    from repro.core.mhd import mhd_total_loss

    priv = lm_mhd_outputs(bundle, params, private_batch)
    pub = lm_mhd_outputs(bundle, params, public_batch)
    loss, metrics = mhd_total_loss(priv, priv["labels"], pub, teacher_outs,
                                   mhd_cfg, rng)
    loss = loss + priv["aux_loss"]  # MoE router aux, if any
    return loss, metrics
