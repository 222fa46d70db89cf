"""Teacher assembly: host milliseconds per fleet step inside the
program's ``wire/decode`` spans (`core/runtime._decode_window`:
deserialise, sample-id check, densify)."""


def read(ctx):
    if ctx.steps == 0:
        return None
    return 1e3 * ctx.span_seconds("wire/decode") / ctx.steps
