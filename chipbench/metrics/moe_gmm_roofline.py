"""Kernel: the grouped matmul of the held experts (`kernels/moe_gmm`,
calls ``moe_gmm`` and ``moe_tgmm``) in the client update, against its
roofline. Each call multiplies one MoE layer-pass's rows; the rows come
from the program's ``moe/rows_held`` counter (`chipbench.moe_counts`),
and every layer-pass of the update makes the same number of calls, so
the calls' rows are the counted rows times calls per layer-pass. The
least time of a call is the larger of its operations over the peak
FLOP/s and its bytes over the peak HBM bandwidth, taken at the mean
rows per call (a lower bound of the sum over calls); the share is that
times the calls over the calls' device time in the trace."""

import bisect

from chipbench import moe_counts

PROGRAM = "jit_update"


def _update_calls(ctx):
    """The grouped-matmul ops that ran inside the update's launches."""
    mods = sorted((m.start, m.end) for m in ctx.reduction.modules
                  if m.name == PROGRAM)
    starts = [s for s, _ in mods]
    out = []
    for e in ctx.reduction.ops:
        if not e.custom or not any(k in e.name for k in moe_counts.KERNELS):
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start <= mods[i][1]:
            out.append(e)
    return out


def read(ctx):
    rows = moe_counts.counter_values("moe/rows_held")
    calls = _update_calls(ctx)
    seconds = sum(e.dur for e in calls)
    if not rows or not calls or seconds <= 0 or ctx.peaks is None:
        return None
    cfg = ctx.cfg
    moe_layers = cfg["hybrid_override_pattern"][
        :cfg["num_hidden_layers"]].count("E")
    # a distilling client step runs its MoE layers over the private and
    # the public batch, a supervised one over the private batch
    layer_passes = moe_layers * ctx.steps * ctx.clients \
        * (1 + ctx.distill_share)
    mean_rows = sum(rows) / layer_passes
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = max(moe_counts.gmm_ops(mean_rows, D, F) / ctx.peaks["flops"],
                moe_counts.gmm_bytes(mean_rows, cfg["experts_held"], D, F)
                / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(calls) / seconds
