"""Expert-parallel MoE with explicit all-to-all dispatch (§Perf, Pair B).

A global dispatch left to XLA SPMD partitioning replicates the (N·k, D)
update stream across the expert ('model') axis and is collective-bound
for 256-expert configs; ``moe.py`` is the one-device path, told which
experts it holds, with no exchange at all.

This module hand-writes the canonical expert-parallel schedule in a fully
manual ``jax.shard_map`` over every mesh axis:

  1. every device routes its LOCAL tokens (cumsum/scatter/gather never
     cross devices) into a capacity-bounded (E, C_dev, D) slot buffer;
  2. one all-to-all over 'model' swaps expert-major slots — per-device
     traffic = tokens_dev · k · D · capacity_factor per direction,
     independent of E;
  3. local experts (E_loc = E/|model|) run as a batched einsum; expert
     weights arrive D-sharded over 'data' (FSDP) and are all-gathered
     per layer (transpose = reduce-scatter for the grads);
  4. the inverse all-to-all returns slots; each device combines its own
     tokens' top-k contributions.

A custom-vjp identity casts cotangents crossing the a2a boundary to bf16 —
otherwise the backward all-to-alls carry f32 (2× ICI traffic).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import MoEConfig
from repro.models.layers import mlp_apply
from repro.models.moe import load_balance_loss, router_topk

MODEL_AXIS = "model"


def _mesh_axes():
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.axis_names:
            return {}
        return dict(zip(mesh.axis_names, mesh.axis_sizes))
    except Exception:
        return {}


@jax.custom_vjp
def _bf16_grad_boundary(x):
    return x


def _bf16_fwd(x):
    return x, None


def _bf16_bwd(_, g):
    return (g.astype(jnp.bfloat16),)


_bf16_grad_boundary.defvjp(_bf16_fwd, _bf16_bwd)


def moe_apply_a2a(params, x, cfg: MoEConfig, act: str = "silu",
                  scoring: str = "softmax") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in replacement for moe.moe_apply's (output, aux loss) when a
    'model' mesh axis exists (falls back to moe.moe_apply otherwise —
    CPU tests)."""
    sizes = _mesh_axes()
    n_model = sizes.get(MODEL_AXIS, 1)
    token_axes = tuple(a for a in ("pod", "data", MODEL_AXIS) if a in sizes)
    n_tok_shards = 1
    for a in token_axes:
        n_tok_shards *= sizes[a]
    data_axes = tuple(a for a in ("pod", "data") if a in sizes)

    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    E, K = cfg.num_experts, cfg.top_k

    if (n_model <= 1 or E % n_model != 0 or xf.shape[0] % n_tok_shards != 0):
        from repro.models.moe import moe_apply

        return moe_apply(params, x, cfg, act, scoring)[:2]

    N_dev = xf.shape[0] // n_tok_shards  # tokens per device
    C = max(int(math.ceil(N_dev * K / E * cfg.capacity_factor)), 1)
    E_loc = E // n_model

    from jax.sharding import PartitionSpec as P

    wg_spec = P(MODEL_AXIS, data_axes if data_axes else None, None)
    wd_spec = P(MODEL_AXIS, None, data_axes if data_axes else None)

    @partial(jax.shard_map,
             in_specs=(P(token_axes, None), P(None, None),
                       wg_spec, wg_spec, wd_spec),
             out_specs=(P(token_axes, None), P(token_axes)),
             axis_names=set(sizes), check_vma=False)
    def local_moe(xt, router_w, w_gate, w_up, w_down):
        # xt: (N_dev, D) — everything below is device-local except the two
        # all-to-alls and the FSDP weight gathers.
        if data_axes:
            w_gate_f = jax.lax.all_gather(w_gate, data_axes, axis=1,
                                          tiled=True)
            w_up_f = jax.lax.all_gather(w_up, data_axes, axis=1, tiled=True)
            w_down_f = jax.lax.all_gather(w_down, data_axes, axis=2,
                                          tiled=True)
        else:
            w_gate_f, w_up_f, w_down_f = w_gate, w_up, w_down

        logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        weights, ids, probs = router_topk(logits, K, scoring)
        aux = load_balance_loss(probs, ids, E)

        flat_ids = ids.reshape(-1)
        onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        flat_pos = jnp.take_along_axis(pos, flat_ids[:, None], axis=1)[:, 0]
        keep = flat_pos < C
        flat_pos_c = jnp.minimum(flat_pos, C - 1)

        upd = jnp.repeat(xt, K, axis=0) * keep[:, None].astype(xt.dtype)
        buf = jnp.zeros((E, C, D), dtype=xt.dtype)
        buf = buf.at[flat_ids, flat_pos_c].add(upd, mode="drop")

        # dispatch a2a over the expert axis
        buf = _bf16_grad_boundary(buf.reshape(n_model, E_loc, C, D))
        recv = jax.lax.all_to_all(buf, MODEL_AXIS, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_model * C, D)

        gate = jnp.einsum("ecd,edf->ecf", recv, w_gate_f,
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("ecd,edf->ecf", recv, w_up_f,
                        preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(recv.dtype)
        out = jnp.einsum("ecf,efd->ecd", h, w_down_f,
                         preferred_element_type=jnp.float32).astype(recv.dtype)

        # inverse a2a: slots back to their source devices
        out = out.reshape(E_loc, n_model, C, D).transpose(1, 0, 2, 3)
        out = _bf16_grad_boundary(out)
        back = jax.lax.all_to_all(out, MODEL_AXIS, split_axis=0,
                                  concat_axis=0, tiled=False)
        out_buf = back.reshape(E, C, D)

        gathered = out_buf[flat_ids, flat_pos_c]
        w = (weights.reshape(-1) * keep.astype(jnp.float32)).astype(xt.dtype)
        y = (gathered * w[:, None]).reshape(N_dev, K, D).sum(axis=1)
        return y, aux[None]

    y, aux = local_moe(xf, params["router"].astype(jnp.float32),
                       params["w_gate"], params["w_up"], params["w_down"])
    aux_loss = jnp.mean(aux) * cfg.router_aux_weight

    if "shared" in params:
        y = y + mlp_apply(params["shared"], xf, act=act)

    return y.reshape(orig_shape), aux_loss
