"""Counts for the grouped matmul of the experts a device holds, and the
program's MoE counters they take their rows from.

The program counts, per client step, the (token, choice) pairs routed to
the experts this device holds, summed over the step's forward passes
and MoE layers (``moe/rows_held``), and the rows of the busiest held
expert, summed likewise (``moe/max_expert_rows``). It emits them as
tracer counters at the step's end (`repro.obs.tracer`); the traced
window's tracer stays readable through ``tracer.last()`` once tracing is
off. A program without them, or without ``last``, gives ``None``.

Every call of the grouped matmul (``moe_gmm`` forward and input
gradient, ``moe_tgmm`` weight gradient) multiplies the rows of one MoE
layer's forward pass by the held experts' weights, one matrix of
``d_model x d_ff`` each:

* operations: ``2 x rows x d_model x d_ff``;
* least bytes: the held experts' matrices once in bfloat16 (the
  operands' type on a TPU), and the rows in (bfloat16) and out
  (float32), ``rows x (d_model + d_ff) x 3`` on average over a call's
  two sides.
"""
from __future__ import annotations

from typing import List, Optional

# the calls' instructions in the trace are named after these functions
# (``moe_gmm.3``; ``transpose_jvp_jit_moe_gmm___.2`` outside a named scope)
KERNELS = ("moe_gmm", "moe_tgmm")


def counter_values(name: str) -> Optional[List[float]]:
    """Every value of the program's counter ``name`` in the last traced
    session, or None where the program emits no such counter."""
    try:
        from repro.obs import tracer
    except ImportError:
        return None
    last = getattr(tracer, "last", None)
    t = last() if last is not None else None
    if t is None:
        return None
    vals = [e["args"]["value"] for e in t.events()
            if e["ph"] == "C" and e["name"] == name]
    return vals or None


def gmm_ops(rows: float, d_model: int, d_ff: int) -> float:
    return 2.0 * rows * d_model * d_ff


def gmm_bytes(rows: float, experts: int, d_model: int, d_ff: int) -> float:
    return 2.0 * experts * d_model * d_ff + 3.0 * rows * (d_model + d_ff)
