"""Kernels (index creation): the share of the HBM roofline that
``cam_match`` and ``bit_transpose`` reach.  The least bytes index creation
moves are the records read once (N x 32 words x 4 B) and the key rows
written once (256 x N / 8 B).  Over the appends whose span lies inside the
traced window: those bytes / 819 GB/s, over the device time of the two
kernels' programs.  Bytes only: the v5e publishes no integer vector
peak."""
LAYER = "kernels (kernels/cam_match.py, kernels/bit_transpose.py)"
UNIT = "%"
MOVES = "ingest_rec_s"

CREATE = r"^jit_(cam_match|bit_transpose)\b"


def read(ctx):
    tr, d = ctx.trace, ctx.gen
    if tr is None or ctx.peaks is None:
        return None
    appends = [s for s in ctx.spans_named("bench.append",
                                          inside=(tr.t0, tr.t1))
               if s.t1 <= tr.t1]
    secs = tr.module_time(CREATE, within=[(s.t0, s.t1) for s in appends])
    if not appends or secs <= 0:
        return None
    records = len(appends) * d.block
    return (100.0 * d.create_bytes(records) / ctx.peaks.hbm_bytes_per_s
            / secs)
