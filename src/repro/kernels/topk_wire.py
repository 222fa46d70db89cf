"""Top-k wire-format packing Pallas TPU kernel (§Perf Pair C).

Packing teacher predictions into (top-k values, indices, logsumexp) is the
MHD exchange wire format. XLA's `lax.top_k` lowers to a full-vocab variadic
sort whose batch dims the SPMD partitioner refuses to shard (measured:
~990 GB of replicated sort buffers at MHD batch sizes — EXPERIMENTS.md
§Perf C1/C2). The jnp fallback is k argmax+mask rounds; this kernel fuses
those rounds in VMEM: one HBM read of the logits row-block, k VPU
max-reductions, and a fused logsumexp — no sort, no second pass.

Row block 8 × vocab ≤ 262144 f32 = 8 MB VMEM working set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30


def _topk_wire_kernel(x_ref, vals_ref, idx_ref, lse_ref, *, k: int,
                      v_total: int):
    x = x_ref[...].astype(jnp.float32)  # (rows, V)
    rows = x.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < v_total, x, _NEG)

    # fused logsumexp (one pass, before masking rounds); a (rows, 1)
    # block, since Mosaic tiles a rank-1 block only at 128 lanes
    m = jnp.max(x, axis=-1, keepdims=True)
    lse_ref[...] = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1,
                                       keepdims=True))

    # the k results are built in registers and stored once: Mosaic cannot
    # store at a lane index that is only known inside the loop
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)

    def round_fn(i, carry):
        cur, vals, idx = carry
        vmax = jnp.max(cur, axis=-1, keepdims=True)  # (rows, 1)
        hit = cur == vmax
        # first index achieving the max
        imax = jnp.min(jnp.where(hit, col, v_total), axis=-1, keepdims=True)
        vals = jnp.where(lane == i, vmax, vals)
        idx = jnp.where(lane == i, imax, idx)
        cur = jnp.where(col == imax, _NEG, cur)
        return cur, vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, k, round_fn,
        (x, jnp.zeros((rows, k), jnp.float32), jnp.zeros((rows, k),
                                                         jnp.int32)))
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def topk_wire(logits, k: int = 32, *, block_rows: int = 8,
              interpret: bool = False):
    """(B, V) -> (vals (B, k) f32, idx (B, k) i32, lse (B,) f32)."""
    B, V = logits.shape
    rows = min(block_rows, B)
    pad = (-B) % rows
    if pad:
        logits = jnp.pad(logits, ((0, pad), (0, 0)))
    Bp = B + pad
    kernel = functools.partial(_topk_wire_kernel, k=k, v_total=V)
    vals, idx, lse = pl.pallas_call(
        kernel,
        grid=(Bp // rows,),
        in_specs=[pl.BlockSpec((rows, V), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, k), lambda i: (i, 0)),
            pl.BlockSpec((rows, k), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(logits)
    return vals[:B], idx[:B], lse[:B, 0]
