"""Front end: mean number of queries coalesced into one wave (``size`` of
the ``coalesce`` spans)."""
LAYER = "front end (serve/service.py)"
UNIT = "queries"
MOVES = "qps"


def read(ctx):
    spans = ctx.spans_named("coalesce")
    if not spans:
        return None
    return sum(s.attrs["size"] for s in spans) / len(spans)
