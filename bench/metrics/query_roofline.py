"""Kernels (query path): the share of the HBM roofline that the query
executors reach.  The least bytes a wave's queries must move are each
distinct key row a query references, unpadded, plus its one output row,
times the row's bytes; that count is fixed by the queries, whatever
kernel implements them.  Over the waves whose ``device.execute`` span lies
inside the traced window: those bytes / 819 GB/s, over the device time of
the executor programs those waves ran."""
LAYER = "kernels (kernels/bitmap_ops.py, engine/bulk.py)"
UNIT = "%"
MOVES = "qps"

#: the bucket executors' compiled programs: the per-pass body (``run``)
#: and the bulk sweep (``run_program``)
EXECUTOR = r"^jit_run(_program)?\b"


def read(ctx):
    tr, d = ctx.trace, ctx.gen
    if tr is None or ctx.peaks is None:
        return None
    waves_of = {s.span_id: s.attrs["wave"] for s in ctx.spans
                if s.name == "coalesce"}
    execs = [s for s in ctx.spans_named("device.execute",
                                        inside=(tr.t0, tr.t1))
             if s.t1 <= tr.t1 and s.parent_id in waves_of]
    waves = {waves_of[s.parent_id] for s in execs}
    ids = d.served.trace_ids
    queries = [ids[s.trace_id] for s in ctx.spans
               if s.name == "queue" and s.attrs.get("wave") in waves
               and s.trace_id in ids]
    secs = tr.module_time(EXECUTOR, within=[(s.t0, s.t1) for s in execs])
    if not queries or secs <= 0:
        return None
    return 100.0 * d.query_bytes(queries) / ctx.peaks.hbm_bytes_per_s / secs
