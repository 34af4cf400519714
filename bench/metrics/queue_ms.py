"""Front end: mean time a query waited in the service's queue (the
``queue`` span, enqueue to wave pickup), in ms."""
LAYER = "front end (serve/service.py)"
UNIT = "ms"
MOVES = "qps"


def read(ctx):
    spans = ctx.spans_named("queue")
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)
