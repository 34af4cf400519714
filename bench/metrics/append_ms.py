"""Session (the ingest entry): the median host time of one
``BitmapDB.append_encoded`` call in the window.  A call returns after the
block's popcount comes back, so it spans the block's host-to-device copy,
index creation, the splice and the readback.  The window's rate also
counts the session changes and every pause of the host; the median of
some 570 calls leaves those out, so it moves less from run to run."""
import numpy as np

LAYER = "session (db/session.py, engine/runtime.py)"
UNIT = "ms"
MOVES = "ingest_rec_s"


def read(ctx):
    ms = getattr(ctx.gen, "append_ms", None)
    return None if ms is None or len(ms) == 0 else float(np.median(ms))
