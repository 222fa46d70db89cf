"""Publish: host milliseconds per fleet step inside the program's
``publish/forward`` (enqueue of the window's teacher forwards) and
``publish/encode`` (top-k wire frame, which blocks on those forwards)
spans (`core/runtime._publish_clients`)."""


def read(ctx):
    if ctx.steps == 0:
        return None
    return 1e3 * (ctx.span_seconds("publish/forward")
                  + ctx.span_seconds("publish/encode")) / ctx.steps
