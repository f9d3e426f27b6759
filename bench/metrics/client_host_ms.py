"""Host time per step in the harness's own work around the index's
calls: drawing the ops, uploading the keys, keeping the results (spans
``client.generate``, ``client.upload``, ``client.book``)."""

SPANS = ("client.generate", "client.upload", "client.book")


def read(ctx):
    if not ctx.steps or not any(k in ctx.spans for k in SPANS):
        return None
    return 1e3 * sum(ctx.spans.get(k, 0.0) for k in SPANS) / ctx.steps
