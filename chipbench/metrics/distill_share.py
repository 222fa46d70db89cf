"""Loop: the share of client steps over the traced steps that distilled
from a teacher (``distill_active`` in the step's metrics); the rest fell
back to a supervised step, which does less work."""


def read(ctx):
    if ctx.steps == 0:
        return None
    return 100.0 * ctx.distill_share
