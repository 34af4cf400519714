"""Session (the ingest entry): the median length of the window's
``ingest.upload`` spans, the host-to-device copy of each appended block
inside ``BitmapDB.append_encoded``, in ms."""
import numpy as np

LAYER = "session (db/session.py, engine/runtime.py)"
UNIT = "ms"
MOVES = "ingest_rec_s"


def read(ctx):
    spans = ctx.spans_named("ingest.upload")
    if not spans:
        return None
    return 1e3 * float(np.median([s.t1 - s.t0 for s in spans]))
