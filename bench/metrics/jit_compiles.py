"""Executors: programs JAX lowered inside the measured window (first-sight
jit shapes, counted by the harness's ``jax.monitoring`` listener).  Set-up
is meant to leave none."""
LAYER = "executors (engine/batch.py, engine/bulk.py)"
UNIT = "programs"
MOVES = "qps"


def read(ctx):
    return float(ctx.compiles)
