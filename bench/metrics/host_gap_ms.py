"""Session (the ingest entry): the device's idle time inside one
``BitmapDB.append_encoded`` call, in ms.  Over the ``ingest.append``
spans that lie wholly inside the traced window: the length of their
intersection with the device's idle gaps, per span.  The host work of an
append that leaves the chip waiting."""
LAYER = "session (db/session.py, engine/runtime.py)"
UNIT = "ms"
MOVES = "ingest_rec_s"


def idle_within(gaps, a, b):
    """Seconds of the intervals ``gaps`` that lie inside ``[a, b]``."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in gaps)


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    appends = [s for s in ctx.spans_named("ingest.append",
                                          inside=(tr.t0, tr.t1))
               if s.t1 <= tr.t1]
    if not appends:
        return None
    gaps = tr.gaps()
    return 1e3 * sum(idle_within(gaps, s.t0, s.t1)
                     for s in appends) / len(appends)
