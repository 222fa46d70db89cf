"""Teacher assembly: host milliseconds per fleet step inside the
program's ``teacher/stack`` spans (`core/runtime._stack_teachers`: pool
sample, staleness gate, the window's frame for the step sliced, uploaded
and stacked). None where the program has no such spans."""

SPAN = "teacher/stack"


def read(ctx):
    if ctx.steps == 0 or ctx.span_count(SPAN) == 0:
        return None
    return 1e3 * ctx.span_seconds(SPAN) / ctx.steps
