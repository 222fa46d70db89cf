"""Client step program: host milliseconds per fleet step inside the
program's ``runtime/dispatch`` spans (`core/runtime.step_client`: the
update's scalar arguments, the jitted update's cache lookup and its
asynchronous call, distilling or supervised). None where the program has
no such spans."""

SPAN = "runtime/dispatch"


def read(ctx):
    if ctx.steps == 0 or ctx.span_count(SPAN) == 0:
        return None
    return 1e3 * ctx.span_seconds(SPAN) / ctx.steps
