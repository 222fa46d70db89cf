"""Experts: how unevenly the held experts are loaded. Rows of the
busiest held expert over the rows of an even split of the rows routed
to this device, each summed over the traced steps' forward passes and
MoE layers (`chipbench.moe_counts`); 1 is even. Rows routed elsewhere
do not count."""

from chipbench import moe_counts


def read(ctx):
    rows = moe_counts.counter_values("moe/rows_held")
    busiest = moe_counts.counter_values("moe/max_expert_rows")
    if not rows or not busiest or sum(rows) <= 0:
        return None
    return sum(busiest) / (sum(rows) / ctx.cfg["experts_held"])
