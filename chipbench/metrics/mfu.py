"""Device, whole step: model FLOPs per fleet step (`chipbench.counts`:
each client's forward and backward on its private and public batch,
plus its publish forwards, no recomputation) times the traced steps,
over the traced window times the chips' peak FLOP/s."""


def read(ctx):
    if ctx.steps == 0 or ctx.peaks is None or ctx.reduction.window_s <= 0:
        return None
    return 100.0 * ctx.flops_per_step * ctx.steps / (
        ctx.reduction.window_s * ctx.peaks["flops"] * ctx.chips)
