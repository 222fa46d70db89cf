"""Wire: bytes the program's `comm.metering.CommMeter` counted on all
edges over the traced steps, per fleet step and client."""


def read(ctx):
    if ctx.steps == 0:
        return None
    return ctx.wire_bytes / (ctx.steps * ctx.clients)
