"""Unified decoder LM covering every assigned architecture family.

Depth is organized as *stages* of scanned repeat-units (config.py). A unit's
parameters are stacked with a leading ``repeats`` dim; the forward pass scans
over them (O(unit) HLO). Heterogeneous layouts — gemma3's 5 local : 1 global,
llama-vision's cross-attention interleave, zamba2's shared attention block,
deepseek's dense-then-MoE split — are all expressed as unit patterns.

Public API (pure functions):
  init_lm(key, cfg)                      -> params
  apply_lm(params, cfg, batch, ...)      -> {"logits", "hidden", "aux_heads", "aux_loss"[, "moe_stats"]}
  lm_loss(params, cfg, batch)            -> (loss, metrics)
  init_lm_cache(cfg, batch, cache_len)   -> caches
  decode_step(params, cfg, token, caches, ...) -> (logits, caches)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.sharding import maybe_shard
from repro.models.config import LayerSpec, ModelConfig, Stage
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _attn_dims(cfg: ModelConfig, cross: bool = False) -> L.AttnDims:
    kv_in = None
    if cross and cfg.vision is not None:
        kv_in = cfg.d_model  # vision tokens are projected to d_model first
    return L.AttnDims(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias,
        qk_norm=cfg.qk_norm,
        kv_input_dim=kv_in,
    )


def _init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    if spec.attn in ("full", "swa"):
        if cfg.mla is not None:
            p["attn"] = MLA.init_mla(ks[0], cfg.d_model, cfg.num_heads, cfg.mla, dtype)
        else:
            p["attn"] = L.init_attention(ks[0], _attn_dims(cfg), dtype)
        p["attn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
    elif spec.attn == "cross":
        p["attn"] = L.init_attention(ks[0], _attn_dims(cfg, cross=True), dtype)
        p["attn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
        p["cross_gate"] = jnp.zeros((), dtype)  # llama-vision tanh gate
    elif spec.attn == "mamba2":
        p["attn"] = SSM.init_mamba2(ks[0], cfg.d_model, cfg.mamba, dtype)
        p["attn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
    elif spec.attn != "none":
        raise ValueError(spec.attn)

    if spec.cross_attn:  # whisper decoder sublayer
        p["xattn"] = L.init_attention(ks[1], _attn_dims(cfg), dtype)
        p["xattn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)

    if spec.ffn == "dense":
        p["ffn"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dtype)
        p["ffn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
    elif spec.ffn == "moe":
        p["ffn"] = MOE.init_moe(ks[2], cfg.d_model, cfg.moe, cfg.act, dtype)
        p["ffn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
    elif spec.ffn == "moe_dense_parallel":  # arctic: dense residual ∥ MoE
        p["ffn"] = MOE.init_moe(ks[2], cfg.d_model, cfg.moe, cfg.act, dtype)
        p["ffn_dense"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.act, dtype)
        p["ffn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
    elif spec.ffn != "none":
        raise ValueError(spec.ffn)
    return p


def _init_unit(key, cfg: ModelConfig, block: Tuple[LayerSpec, ...], dtype):
    keys = jax.random.split(key, len(block))
    return {f"layer{i}": _init_layer(keys[i], cfg, spec, dtype)
            for i, spec in enumerate(block)}


def init_lm(key, cfg: ModelConfig, dtype=jnp.float32):
    cfg.validate()
    n_stages = len(cfg.stages)
    keys = jax.random.split(key, n_stages + 10)
    params: Dict[str, Any] = {
        "embed": L.embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm, dtype),
    }
    for si, stage in enumerate(cfg.stages):
        unit_keys = jax.random.split(keys[1 + si], stage.repeats)
        params[f"stage{si}"] = jax.vmap(
            lambda k: _init_unit(k, cfg, stage.block, dtype)
        )(unit_keys)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[n_stages + 1], cfg.d_model,
                                         cfg.vocab_size, dtype)
    if cfg.num_aux_heads:
        params["aux_heads"] = (
            jax.random.normal(keys[n_stages + 2],
                              (cfg.num_aux_heads, cfg.d_model, cfg.vocab_size))
            * (1.0 / math.sqrt(cfg.d_model))
        ).astype(dtype)
    if any(s.shared_attn for st in cfg.stages for s in st.block):
        params["shared_attn"] = L.init_attention(keys[n_stages + 3],
                                                 _attn_dims(cfg), dtype)
        params["shared_attn_norm"] = L.init_norm(cfg.d_model, cfg.norm, dtype)
    if cfg.vision is not None:
        params["vision_proj"] = L.dense_init(keys[n_stages + 4],
                                             cfg.vision.embed_dim,
                                             cfg.d_model, dtype)
    if cfg.audio is not None:
        params["audio_proj"] = L.dense_init(keys[n_stages + 5],
                                            cfg.audio.frame_dim,
                                            cfg.d_model, dtype)
        params["encoder"] = _init_encoder(keys[n_stages + 6], cfg, dtype)
    if cfg.pos_embed == "learned":
        params["pos_embed"] = (jax.random.normal(
            keys[n_stages + 7], (cfg.max_seq_len, cfg.d_model)) * 0.02).astype(dtype)
    if cfg.mtp:
        params["mtp"] = {
            "proj": L.dense_init(keys[n_stages + 8], 2 * cfg.d_model,
                                 cfg.d_model, dtype),
            "norm": L.init_norm(cfg.d_model, cfg.norm, dtype),
            "layer": _init_layer(keys[n_stages + 9], cfg,
                                 LayerSpec(attn="full", ffn="dense"), dtype),
        }
    return params


def _init_encoder(key, cfg: ModelConfig, dtype):
    enc = cfg.encoder
    keys = jax.random.split(key, 2)
    spec = LayerSpec(attn="full", ffn="dense")
    unit_keys = jax.random.split(keys[0], enc.num_layers)
    return {
        "stage0": jax.vmap(lambda k: _init_unit(k, cfg, (spec,), dtype))(unit_keys),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm, dtype),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _sinusoidal(T: int, D: int) -> jnp.ndarray:
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    dim = jnp.arange(D // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2 * dim / D)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _layer_forward(lp, cfg: ModelConfig, spec: LayerSpec, x, *,
                   shared_attn_params, cross_src, enc_out, mask_kind_override=None):
    """One layer (full-sequence path). Returns (x, aux_loss, moe_stats):
    moe_stats as `moe.moe_apply` returns them, zeros without experts."""
    aux = jnp.zeros((), jnp.float32)
    stats = jnp.zeros((2,), jnp.float32)
    rope = cfg.rope_theta if cfg.pos_embed == "rope" else None

    if spec.attn in ("full", "swa"):
        h = L.norm_apply(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        if cfg.mla is not None:
            a = MLA.mla_apply(lp["attn"], h, cfg.mla, cfg.num_heads,
                              rope_theta=cfg.rope_theta)
        else:
            mask_kind = mask_kind_override or ("swa" if spec.attn == "swa" else "causal")
            a = L.attention_apply(
                lp["attn"], _attn_dims(cfg), h,
                mask_kind=mask_kind, window=cfg.window_size,
                rope_theta=rope, logit_softcap=cfg.attn_logit_softcap)
        x = x + a
    elif spec.attn == "cross":
        h = L.norm_apply(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        a = L.attention_apply(
            lp["attn"], _attn_dims(cfg, cross=True), h,
            mask_kind="none", kv_src=cross_src, rope_theta=None)
        x = x + jnp.tanh(lp["cross_gate"]).astype(x.dtype) * a
    elif spec.attn == "mamba2":
        h = L.norm_apply(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        x = x + SSM.mamba2_apply(lp["attn"], h, cfg.mamba, eps=cfg.norm_eps)

    if spec.shared_attn:
        h = L.norm_apply(shared_attn_params["norm"], x, cfg.norm, cfg.norm_eps)
        a = L.attention_apply(
            shared_attn_params["attn"], _attn_dims(cfg), h,
            mask_kind="causal", rope_theta=rope)
        x = x + a

    if spec.cross_attn:
        h = L.norm_apply(lp["xattn_norm"], x, cfg.norm, cfg.norm_eps)
        a = L.attention_apply(
            lp["xattn"], _attn_dims(cfg), h,
            mask_kind="none", kv_src=enc_out, rope_theta=None)
        x = x + a

    if spec.ffn == "dense":
        h = L.norm_apply(lp["ffn_norm"], x, cfg.norm, cfg.norm_eps)
        x = x + L.mlp_apply(lp["ffn"], h, cfg.act)
    elif spec.ffn in ("moe", "moe_dense_parallel"):
        h = L.norm_apply(lp["ffn_norm"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe_impl == "a2a":
            from repro.models.moe_a2a import moe_apply_a2a

            y, moe_aux = moe_apply_a2a(lp["ffn"], h, cfg.moe, cfg.act,
                                       scoring=cfg.moe_scoring)
        else:
            y, moe_aux, stats = MOE.moe_apply(lp["ffn"], h, cfg.moe, cfg.act,
                                              scoring=cfg.moe_scoring)
        if spec.ffn == "moe_dense_parallel":
            y = y + L.mlp_apply(lp["ffn_dense"], h, cfg.act)
        x = x + y
        aux = aux + moe_aux
    x = maybe_shard(x, "batch", "seq", "model")
    return x, aux, stats


def _run_stages(params, cfg: ModelConfig, x, stages, prefix, *,
                shared_attn_params=None, cross_src=None, enc_out=None,
                mask_kind_override=None):
    """Scan every stage's stacked units over x. Returns (x, total_aux,
    moe_stats summed over the layers)."""
    total = (jnp.zeros((), jnp.float32), jnp.zeros((2,), jnp.float32))

    for si, stage in enumerate(stages):
        stacked = params[f"{prefix}{si}"]

        def unit_fn(carry, unit_params, _stage=stage):
            h, (aux_acc, stats_acc) = carry
            for li, spec in enumerate(_stage.block):
                h, aux, stats = _layer_forward(
                    unit_params[f"layer{li}"], cfg, spec, h,
                    shared_attn_params=shared_attn_params,
                    cross_src=cross_src, enc_out=enc_out,
                    mask_kind_override=mask_kind_override)
                aux_acc = aux_acc + aux
                stats_acc = stats_acc + stats
            return (h, (aux_acc, stats_acc)), None

        if cfg.remat != "none":
            # inside a scan the recomputation cannot be merged with the
            # forward; a unit called once needs CSE prevented, or XLA
            # keeps the forward's activations and the remat saves nothing
            unit_fn = jax.checkpoint(unit_fn,
                                     prevent_cse=stage.repeats == 1)

        r1 = _nested_factor(stage.repeats) if cfg.remat == "nested" else 0
        if stage.repeats == 1:
            (x, total), _ = unit_fn(
                (x, total), jax.tree.map(lambda a: a[0], stacked))
        elif r1:
            # √-depth remat: outer scan over r1 groups, each group a
            # checkpointed inner scan over r2 units — residual stacks hold
            # r1 + r2 activations instead of r1·r2 (§Perf lever)
            r2 = stage.repeats // r1

            def group_fn(carry, group_params):
                return jax.lax.scan(unit_fn, carry, group_params)

            grouped = jax.tree.map(
                lambda a: a.reshape((r1, r2) + a.shape[1:]), stacked)
            (x, total), _ = jax.lax.scan(
                jax.checkpoint(group_fn, prevent_cse=False),
                (x, total), grouped)
        else:
            (x, total), _ = jax.lax.scan(unit_fn, (x, total), stacked)
    return (x,) + total


def _nested_factor(repeats: int) -> int:
    """Largest r1 <= sqrt(repeats) dividing repeats; 0 if not worthwhile."""
    if repeats < 8:
        return 0
    r1 = int(math.sqrt(repeats))
    while r1 > 1 and repeats % r1:
        r1 -= 1
    return r1 if r1 > 1 else 0


def _embed_tokens(params, cfg: ModelConfig, tokens):
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    return x


def _add_positional(params, cfg: ModelConfig, x, offset: int = 0):
    T = x.shape[1]
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], offset, T, axis=0)[None].astype(x.dtype)
    elif cfg.pos_embed == "sinusoidal":
        x = x + _sinusoidal(T, cfg.d_model)[None].astype(x.dtype)
    return x


def encode_audio(params, cfg: ModelConfig, frames):
    """Whisper encoder over stub frame embeddings (B, T_enc, frame_dim)."""
    x = jnp.einsum("btf,fd->btd", frames, params["audio_proj"],
                   preferred_element_type=jnp.float32).astype(frames.dtype)
    x = x + _sinusoidal(x.shape[1], cfg.d_model)[None].astype(x.dtype)
    x = maybe_shard(x, "batch", "seq", "model")
    enc_stage = (Stage(block=(LayerSpec(attn="full", ffn="dense"),),
                       repeats=cfg.encoder.num_layers),)
    x, _, _ = _run_stages(params["encoder"], cfg, x, enc_stage, "stage",
                       mask_kind_override="none")
    return L.norm_apply(params["encoder"]["final_norm"], x, cfg.norm, cfg.norm_eps)


def _heads(params, cfg: ModelConfig, hidden):
    """Main + aux logits from final hidden states."""
    head_w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("...d,dv->...v", hidden, head_w,
                        preferred_element_type=jnp.float32)
    logits = maybe_shard(logits, "batch", "seq", "model")
    aux_logits = None
    if cfg.num_aux_heads:
        aux_logits = jnp.einsum("...d,mdv->m...v", hidden, params["aux_heads"],
                                preferred_element_type=jnp.float32)
    return logits, aux_logits


def apply_lm(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Full-sequence forward.

    batch: {"tokens": (B,T)} plus optionally "vision_embeds" (B,P,v_dim)
    or "audio_frames" (B,T_enc,f_dim).
    Returns dict with hidden (B,T,D), logits (B,T,V), aux_heads (m,B,T,V)|None,
    aux_loss scalar, (with experts) moe_stats (2,) = rows routed to the
    held experts and rows of the busiest one, summed over the MoE layers,
    and (if cfg.mtp) mtp_hidden.
    """
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, tokens)
    x = _add_positional(params, cfg, x)
    x = maybe_shard(x, "batch", "seq", "model")

    cross_src = None
    if cfg.vision is not None:
        v = batch["vision_embeds"]
        cross_src = jnp.einsum("bpe,ed->bpd", v, params["vision_proj"],
                               preferred_element_type=jnp.float32).astype(x.dtype)
    enc_out = None
    if cfg.audio is not None:
        enc_out = encode_audio(params, cfg, batch["audio_frames"])

    shared = None
    if "shared_attn" in params:
        shared = {"attn": params["shared_attn"],
                  "norm": params["shared_attn_norm"]}

    x, aux_loss, moe_stats = _run_stages(params, cfg, x, cfg.stages, "stage",
                                         shared_attn_params=shared,
                                         cross_src=cross_src, enc_out=enc_out)
    hidden = L.norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits, aux_logits = _heads(params, cfg, hidden)

    out = {"hidden": hidden, "logits": logits, "aux_heads": aux_logits,
           "aux_loss": aux_loss}
    if cfg.moe is not None:
        out["moe_stats"] = moe_stats

    if cfg.mtp:
        # DeepSeek MTP: predict t+2 from [h_t ; emb(tok_{t+1})]
        emb_next = _embed_tokens(params, cfg, jnp.roll(tokens, -1, axis=1))
        mtp_in = jnp.concatenate([hidden, emb_next.astype(hidden.dtype)], axis=-1)
        h = jnp.einsum("...e,ed->...d", mtp_in, params["mtp"]["proj"],
                       preferred_element_type=jnp.float32).astype(hidden.dtype)
        h = L.norm_apply(params["mtp"]["norm"], h, cfg.norm, cfg.norm_eps)
        h, _, _ = _layer_forward(params["mtp"]["layer"], cfg,
                              LayerSpec(attn="full", ffn="dense"), h,
                              shared_attn_params=None, cross_src=None,
                              enc_out=None)
        out["mtp_hidden"] = h
    return out


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits, labels, valid=None):
    """Mean next-token CE. logits (..., V) fp32; labels int."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if valid is not None:
        nll = nll * valid
        return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)
    return jnp.mean(nll)


def _chunked_xent(hidden, head_w, labels, chunk: int):
    """CE without materializing (B, T, V) logits all at once.

    §Perf lever: for 262k vocabs the full logit tensor dominates activation
    memory. Chunking is along TIME — each (B, chunk_t, D) slice keeps the
    batch sharding intact (flat-token chunks would concentrate a chunk on a
    subset of devices and force gathers). Per-chunk remat keeps the scan
    from stacking chunk logits as backward residuals.
    """
    B, T, D = hidden.shape
    n = B * T
    pad = (-T) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    nchunks = hidden.shape[1] // chunk
    hs = hidden.reshape(B, nchunks, chunk, D).swapaxes(0, 1)
    ls = labels.reshape(B, nchunks, chunk).swapaxes(0, 1)
    valid = (jnp.arange(hidden.shape[1]) < T).reshape(
        nchunks, chunk).astype(jnp.float32)

    def body(acc, xs):
        h, lab, v = xs
        logits = jnp.einsum("bcd,dv->bcv", h, head_w,
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((logz - ll) * v[None, :]), None

    total, _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                            jnp.zeros((), jnp.float32), (hs, ls, valid))
    return total / n


def lm_loss(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Next-token loss (tokens shifted internally); returns (loss, metrics)."""
    out = apply_lm(params, cfg, batch)
    tokens = batch["tokens"]
    labels = tokens[:, 1:]
    if cfg.loss_impl == "chunked":
        head_w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ce = _chunked_xent(out["hidden"][:, :-1], head_w, labels, cfg.loss_chunk)
    else:
        ce = softmax_xent(out["logits"][:, :-1].astype(jnp.float32), labels)
    loss = ce + out["aux_loss"]
    metrics = {"ce": ce, "aux_loss": out["aux_loss"]}
    if cfg.mtp:
        head_w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        mtp_logits = jnp.einsum("btd,dv->btv", out["mtp_hidden"][:, :-2], head_w,
                                preferred_element_type=jnp.float32)
        mtp_ce = softmax_xent(mtp_logits, tokens[:, 2:])
        loss = loss + 0.3 * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    return loss, metrics


# ---------------------------------------------------------------------------
# decode (serve path)
# ---------------------------------------------------------------------------

def _layer_cache_shape(cfg: ModelConfig, spec: LayerSpec, batch: int,
                       cache_len: int, dtype):
    caches = {}
    if spec.attn in ("full", "swa"):
        # enc-dec (whisper): self-attn cache is decoder-length; cache_len is
        # the encoder frame count (used by the cross-attn cache below)
        self_len = cfg.audio.decoder_len if cfg.audio is not None else cache_len
        if cfg.mla is not None:
            caches["attn"] = MLA.init_mla_cache(batch, self_len, cfg.mla, dtype)
        else:
            length = min(cfg.window_size, self_len) if spec.attn == "swa" else self_len
            caches["attn"] = L.init_kv_cache(batch, length, cfg.num_kv_heads,
                                             cfg.resolved_head_dim, dtype)
    elif spec.attn == "mamba2":
        caches["attn"] = SSM.init_mamba2_cache(batch, cfg.d_model, cfg.mamba, dtype)
    elif spec.attn == "cross":
        caches["attn"] = {
            "k": jnp.zeros((batch, cfg.vision.num_patches, cfg.num_kv_heads,
                            cfg.resolved_head_dim), dtype),
            "v": jnp.zeros((batch, cfg.vision.num_patches, cfg.num_kv_heads,
                            cfg.resolved_head_dim), dtype),
        }
    if spec.shared_attn:
        caches["shared_attn"] = L.init_kv_cache(batch, cache_len, cfg.num_kv_heads,
                                                cfg.resolved_head_dim, dtype)
    if spec.cross_attn:
        enc_len = cache_len  # encoder length for whisper decode
        caches["xattn"] = {
            "k": jnp.zeros((batch, enc_len, cfg.num_kv_heads,
                            cfg.resolved_head_dim), dtype),
            "v": jnp.zeros((batch, enc_len, cfg.num_kv_heads,
                            cfg.resolved_head_dim), dtype),
        }
    return caches


def init_lm_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  dtype=jnp.bfloat16):
    """Nested cache pytree mirroring the stage structure (stacked per unit)."""
    caches = {}
    for si, stage in enumerate(cfg.stages):
        unit = {f"layer{li}": _layer_cache_shape(cfg, spec, batch, cache_len, dtype)
                for li, spec in enumerate(stage.block)}
        caches[f"stage{si}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (stage.repeats,) + a.shape), unit)
    caches["index"] = jnp.zeros((), jnp.int32)
    return caches


def _cross_decode(attn_params, cfg, x, cache):
    dims = _attn_dims(cfg)
    B = x.shape[0]
    q = jnp.einsum("...d,dh->...h", x, attn_params["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    q = q.reshape(B, 1, dims.num_heads, dims.head_dim)
    out = L.attention_scores(q, cache["k"].astype(x.dtype),
                             cache["v"].astype(x.dtype), None)
    out = out.reshape(B, 1, dims.num_heads * dims.head_dim)
    return jnp.einsum("...h,hd->...d", out, attn_params["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _layer_decode(lp, cfg: ModelConfig, spec: LayerSpec, x, cache, *,
                  shared_attn_params):
    rope = cfg.rope_theta if cfg.pos_embed == "rope" else None
    new_cache = dict(cache)
    if spec.attn in ("full", "swa"):
        h = L.norm_apply(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        if cfg.mla is not None:
            a, new_cache["attn"] = MLA.mla_decode(
                lp["attn"], h, cache["attn"], cfg.mla, cfg.num_heads,
                rope_theta=cfg.rope_theta)
        else:
            window = cfg.window_size if spec.attn == "swa" else 0
            a, new_cache["attn"] = L.attention_decode(
                lp["attn"], _attn_dims(cfg), h, cache["attn"],
                window=window, rope_theta=rope,
                logit_softcap=cfg.attn_logit_softcap)
        x = x + a
    elif spec.attn == "cross":
        h = L.norm_apply(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        a = _cross_decode(lp["attn"], cfg, h, cache["attn"])
        x = x + jnp.tanh(lp["cross_gate"]).astype(x.dtype) * a
    elif spec.attn == "mamba2":
        h = L.norm_apply(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        a, new_cache["attn"] = SSM.mamba2_decode(lp["attn"], h, cache["attn"],
                                                 cfg.mamba, eps=cfg.norm_eps)
        x = x + a

    if spec.shared_attn:
        h = L.norm_apply(shared_attn_params["norm"], x, cfg.norm, cfg.norm_eps)
        a, new_cache["shared_attn"] = L.attention_decode(
            shared_attn_params["attn"], _attn_dims(cfg), h,
            cache["shared_attn"], rope_theta=rope)
        x = x + a

    if spec.cross_attn:
        h = L.norm_apply(lp["xattn_norm"], x, cfg.norm, cfg.norm_eps)
        x = x + _cross_decode(lp["xattn"], cfg, h, cache["xattn"])

    if spec.ffn == "dense":
        h = L.norm_apply(lp["ffn_norm"], x, cfg.norm, cfg.norm_eps)
        x = x + L.mlp_apply(lp["ffn"], h, cfg.act)
    elif spec.ffn in ("moe", "moe_dense_parallel"):
        h = L.norm_apply(lp["ffn_norm"], x, cfg.norm, cfg.norm_eps)
        y, _, _ = MOE.moe_apply(lp["ffn"], h, cfg.moe, cfg.act,
                                scoring=cfg.moe_scoring)
        if spec.ffn == "moe_dense_parallel":
            y = y + L.mlp_apply(lp["ffn_dense"], h, cfg.act)
        x = x + y
    return x, new_cache


def prefill_cross_caches(params, cfg: ModelConfig, caches, *,
                         vision_embeds=None, audio_frames=None):
    """Fill cross-attention K/V caches from the modality source.

    Must run once before decode for VLM (vision cross layers) and enc-dec
    (whisper decoder cross sublayers). Returns updated caches.
    """
    cross_src = None
    if vision_embeds is not None:
        cross_src = jnp.einsum("bpe,ed->bpd", vision_embeds,
                               params["vision_proj"],
                               preferred_element_type=jnp.float32
                               ).astype(vision_embeds.dtype)
    enc_out = None
    if audio_frames is not None:
        enc_out = encode_audio(params, cfg, audio_frames)

    dims = _attn_dims(cfg)
    KV, hd = dims.num_kv_heads, dims.head_dim

    def kv_for(stacked_wk, stacked_wv, src):
        # stacked_w*: (R, D_src, KV*hd); src: (B, S, D_src)
        k = jnp.einsum("bsd,rdh->rbsh", src, stacked_wk,
                       preferred_element_type=jnp.float32)
        v = jnp.einsum("bsd,rdh->rbsh", src, stacked_wv,
                       preferred_element_type=jnp.float32)
        R, B, S, _ = k.shape
        return (k.reshape(R, B, S, KV, hd), v.reshape(R, B, S, KV, hd))

    caches = jax.tree.map(lambda x: x, caches)  # shallow copy
    for si, stage in enumerate(cfg.stages):
        for li, spec in enumerate(stage.block):
            lp = params[f"stage{si}"][f"layer{li}"]
            layer_cache = dict(caches[f"stage{si}"][f"layer{li}"])
            if spec.attn == "cross" and cross_src is not None:
                k, v = kv_for(lp["attn"]["wk"], lp["attn"]["wv"], cross_src)
                tgt = layer_cache["attn"]
                layer_cache["attn"] = {**tgt, "k": k.astype(tgt["k"].dtype),
                                       "v": v.astype(tgt["v"].dtype)}
            if spec.cross_attn and enc_out is not None:
                k, v = kv_for(lp["xattn"]["wk"], lp["xattn"]["wv"], enc_out)
                tgt = layer_cache["xattn"]
                layer_cache["xattn"] = {**tgt, "k": k.astype(tgt["k"].dtype),
                                        "v": v.astype(tgt["v"].dtype)}
            stage_cache = dict(caches[f"stage{si}"])
            stage_cache[f"layer{li}"] = layer_cache
            caches[f"stage{si}"] = stage_cache
    return caches


def decode_step(params, cfg: ModelConfig, token, caches):
    """One-token decode. token: (B, 1) int32. Returns (logits (B,1,V), caches)."""
    x = _embed_tokens(params, cfg, token)
    x = _add_positional(params, cfg, x, offset=0) if cfg.pos_embed != "learned" else (
        x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], caches["index"] % cfg.max_seq_len, 1, axis=0
        )[None].astype(x.dtype))
    x = maybe_shard(x, "batch", "seq", "model")

    shared = None
    if "shared_attn" in params:
        shared = {"attn": params["shared_attn"],
                  "norm": params["shared_attn_norm"]}

    new_caches = {"index": caches["index"] + 1}
    for si, stage in enumerate(cfg.stages):
        stacked_p = params[f"stage{si}"]
        stacked_c = caches[f"stage{si}"]

        def unit_fn(h, xs, _stage=stage):
            unit_params, unit_cache = xs
            new_unit_cache = {}
            for li, spec in enumerate(_stage.block):
                h, new_unit_cache[f"layer{li}"] = _layer_decode(
                    unit_params[f"layer{li}"], cfg, spec, h,
                    unit_cache[f"layer{li}"], shared_attn_params=shared)
            return h, new_unit_cache

        if stage.repeats == 1:
            first = lambda a: a[0]
            x, uc = unit_fn(x, (jax.tree.map(first, stacked_p),
                                jax.tree.map(first, stacked_c)))
            new_caches[f"stage{si}"] = jax.tree.map(lambda a: a[None], uc)
        else:
            x, new_caches[f"stage{si}"] = jax.lax.scan(
                unit_fn, x, (stacked_p, stacked_c))

    hidden = L.norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits, _ = _heads(params, cfg, hidden)
    return logits, new_caches
