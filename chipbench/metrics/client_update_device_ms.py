"""Client step program: device milliseconds per fleet step in the
launches of the jitted client update (`core/runtime._client_update`'s
``update``, named ``jit_update`` in the trace)."""

PROGRAM = "jit_update"


def read(ctx):
    seconds = ctx.reduction.module_seconds(PROGRAM)
    if ctx.steps == 0 or seconds <= 0:
        return None
    return 1e3 * seconds / ctx.steps
