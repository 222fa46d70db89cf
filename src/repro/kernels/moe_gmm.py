"""Grouped matrix multiplication over the experts a device holds.

``gmm(lhs, rhs, group_sizes)`` multiplies each group of rows of ``lhs``
(m, k) by its own matrix of ``rhs`` (groups, k, n): rows
``[sizes[:i].sum(), sizes[:i + 1].sum())`` by ``rhs[i]``. Rows past the
last group are read by no group, and their output rows are left
unwritten: the caller masks them.

The kernels are the Pallas grouped matmuls that ship with JAX
(``jax.experimental.pallas.ops.tpu.megablox``), with their gradient
(``dlhs = gmm(g, rhsᵀ)``, ``drhs = tgmm(lhsᵀ, g)``). Each runs inside a
jitted function of this module, ``moe_gmm`` or ``moe_tgmm``, so that its
custom call in a profile carries that name. Operands are multiplied in
``compute_dtype`` with float32 accumulation and float32 results, as
XLA's default precision multiplies float32 on a TPU.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module, not the package's differentiable ``gmm`` of the same name
_MB = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

TILING = (128, 128, 128)  # rows, contraction, columns per tile


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def moe_gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
            interpret: bool = False):
    """(m, k) x (groups, k, n) -> (m, n) float32, per group of rows."""
    return _MB.gmm.__wrapped__(lhs, rhs, group_sizes, jnp.float32, TILING,
                               transpose_rhs=transpose_rhs,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_tgmm(lhs, grad, group_sizes, *, interpret: bool = False):
    """(m, k), (m, n) -> (groups, k, n) float32: per group, the sum over
    its rows of lhs_rowᵀ grad_row."""
    return _MB.tgmm.__wrapped__(lhs.swapaxes(0, 1), grad, group_sizes,
                                jnp.float32, TILING, None,
                                group_sizes.shape[0], interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, compute_dtype, interpret):
    return moe_gmm(lhs.astype(compute_dtype), rhs.astype(compute_dtype),
                   group_sizes, interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, compute_dtype, interpret):
    return _gmm(lhs, rhs, group_sizes, compute_dtype, interpret), \
        (lhs, rhs, group_sizes)


def _gmm_bwd(compute_dtype, interpret, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(compute_dtype)
    dlhs = moe_gmm(g, rhs.astype(compute_dtype), group_sizes,
                   transpose_rhs=True, interpret=interpret)
    drhs = moe_tgmm(lhs.astype(compute_dtype), g, group_sizes,
                    interpret=interpret)
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs, rhs, group_sizes, *, compute_dtype=jnp.bfloat16,
        interpret: bool = False):
    """Differentiable grouped matmul; ``lhs`` rows are padded to a whole
    number of row tiles, which no group reads."""
    m = lhs.shape[0]
    pad = (-m) % TILING[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32),
               jnp.dtype(compute_dtype), interpret)
    return out[:m]
