"""Planner / cost model: key rows streamed per served query.  Each bucket
dispatch streams G x P x L literal rows for each of its queries, the query
axis padded to a power of two (``bucket.dispatch`` spans: ``shape`` and
``q``)."""
LAYER = "planner / cost model (engine/planner.py, engine/costmodel.py)"
UNIT = "rows"
MOVES = "qps"


def _pow2(x):
    p = 1
    while p < x:
        p *= 2
    return p


def read(ctx):
    spans = ctx.spans_named("bucket.dispatch")
    served = sum(s.attrs["q"] for s in spans)
    if not served:
        return None
    rows = 0
    for s in spans:
        g, p, l = s.attrs["shape"]
        rows += _pow2(s.attrs["q"]) * g * p * l
    return rows / served
