"""Device: share of the traced window in which no operation ran on the
chip, in the serving cell."""
LAYER = "device"
UNIT = "%"
MOVES = "p99_ms"


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
