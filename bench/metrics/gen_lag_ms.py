"""Load generator: p99 of how late the generator submitted each query of
the window (submit time minus due time), in ms.  A starved generator
would otherwise read as a fast server."""
import numpy as np

LAYER = "load generator (bench/traffic.py)"
UNIT = "ms"
MOVES = "p99_ms"


def read(ctx):
    lag = ctx.gen.lag_ms
    lag = lag[~np.isnan(lag)]
    return float(np.percentile(lag, 99)) if lag.size else None
