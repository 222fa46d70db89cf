"""Input batches: host milliseconds per fleet step inside the program's
``data/private`` (a client's private batch drawn and uploaded,
`core/runtime.step_client`), ``data/public`` (the step's public batch,
`step`) and ``data/publish`` (the publish round's window of public
batches, `_publish_clients`) spans. None where the program has no such
spans."""

SPANS = ("data/private", "data/public", "data/publish")


def read(ctx):
    if ctx.steps == 0 or not any(ctx.span_count(n) for n in SPANS):
        return None
    return 1e3 * sum(ctx.span_seconds(n) for n in SPANS) / ctx.steps
