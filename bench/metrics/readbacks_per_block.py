"""Session (the ingest entry): blocking device-to-host reads that
``BitmapDB.append_encoded`` made per appended block in the window (the
change of the ``db_ingest_readbacks_total`` counter over the blocks
appended).  A pipelined ingest path drives it below 1."""
LAYER = "session (db/session.py, engine/runtime.py)"
UNIT = "reads"
MOVES = "ingest_rec_s"

COUNTER = "db_ingest_readbacks_total"


def read(ctx):
    reads = ctx.counters.get(COUNTER)
    blocks = getattr(ctx.gen, "blocks_done", 0)
    if reads is None or not blocks:
        return None
    return reads / blocks
