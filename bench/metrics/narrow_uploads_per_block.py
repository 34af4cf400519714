"""Session (the ingest entry): host record blocks that
``BitmapDB.append_encoded`` sent to the device at the session's narrow
width (uint8 up to 256 keys) per appended block in the window (the
change of the ``db_ingest_narrow_uploads_total`` counter over the blocks
appended).  1 when every block crossed narrowed, 0 when every block
crossed as int32."""
LAYER = "session (db/session.py, engine/runtime.py)"
UNIT = "uploads"
MOVES = "ingest_rec_s"

COUNTER = "db_ingest_narrow_uploads_total"


def read(ctx):
    uploads = ctx.counters.get(COUNTER)
    blocks = getattr(ctx.gen, "blocks_done", 0)
    if uploads is None or not blocks:
        return None
    return uploads / blocks
