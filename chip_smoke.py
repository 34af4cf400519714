"""Chip smoke: the system's main path end to end on a TPU, at a real size.

One process, one chip (``python chip_smoke.py``):

  1. **Data** — 2^23 records from ``--seed`` under a schema of 32
     categorical columns with 32 values each: 1,024 key rows, 32 words per
     record (the paper's record width).  The resident index is 1,024 key
     rows x 262,144 words = 1 GiB of HBM.
  2. **Ingest** — ``BitmapDB(schema, path=<temp dir>, backend="auto")``
     appends 2^18-record blocks; ``spill_records`` leaves two segments on
     disk and a WAL tail.
  3. **Serve** — a warmed-up ``BitmapService`` answers 1,024 DSL queries of
     the seven-family mix from 8 caller threads.  Every count, and the ids
     of a seeded sample of 64 queries, must equal a plain NumPy evaluation
     of the same predicate on the raw records; the sample is also run on
     each kernel-backed query backend by name.
  4. **Recover** — the session closes, ``repro.open`` recovers it from
     segments + WAL, and the sample must answer the same again.

Any fallback fails the smoke: a degraded, retried or failed wave, an open
breaker, a bucket served by the ``ref`` backend, or index creation off the
Pallas kernels.  Timings printed on the way are smoke timings, not
benchmark results.

``--chips 4`` runs only the two paths that exist across chips, in one
process: the ``shard_map`` index build on a 4-chip mesh against the
one-device build, and ``FabricClient.local`` over four hash shards, each
``BitmapDB`` pinned to its own chip, against NumPy.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
without a TPU the script exits non-zero before printing it.

Usage: python chip_smoke.py [--seed N] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import faulthandler
import functools
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

COLUMNS = 32                  # categorical columns = words per record
VALUES = 32                   # values per column -> 32 x 32 = 1,024 keys
RECORDS = 1 << 23             # 8,388,608 records -> 262,144-word key rows
BLOCK = 1 << 18               # records per append
SPILL = 3 << 20               # spill threshold: 2 segments + a WAL tail
QUERIES = 1024
SAMPLE = 64
CALLERS = 8
MAX_BATCH = 256               # the service's widest coalesced wave
#: past this many seconds the smoke dumps every thread's stack and exits 1:
#: a hang fails inside the 1,200 s a run may take, and shows where it hung
WATCHDOG_S = 1100


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ data
def column_names(columns: int = COLUMNS) -> list[str]:
    return [f"c{i:02d}" for i in range(columns)]


def make_schema(columns: int = COLUMNS, values: int = VALUES):
    from repro.db import Column, Schema
    return Schema([Column.categorical(name, range(values))
                   for name in column_names(columns)])


def make_values(seed: int, records: int, columns: int = COLUMNS,
                values: int = VALUES) -> np.ndarray:
    """Raw column values, column-major ``(columns, records)`` uint8."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, values, (columns, records), dtype=np.uint8)


# --------------------------------------------------------------- queries
# A query is a small tree: ("lit", column, value) | ("not", t) |
# ("and", [t, ...]) | ("or", [t, ...]).  It renders both as a repro DSL
# expression and as a NumPy evaluation over the raw values, so the
# reference never goes through repro.
def make_queries(seed: int, count: int, columns: int = COLUMNS,
                 values: int = VALUES) -> list:
    """The seven plan-shape families of the serving mix: single literals,
    AND chains with negation, OR-of-AND trees and pure ORs."""
    rng = np.random.default_rng(seed)

    def lit():
        return ("lit", int(rng.integers(0, columns)),
                int(rng.integers(0, values)))

    out = []
    for i in range(count):
        fam = i % 7
        if fam == 0:
            t = lit()
        elif fam == 1:
            t = ("and", [lit(), ("not", lit())])
        elif fam == 2:
            t = ("and", [lit(), lit(), ("not", lit())])
        elif fam == 3:
            t = ("and", [("or", [lit(), lit()]), lit()])
        elif fam == 4:
            t = ("and", [("or", [lit(), lit()]), ("or", [lit(), lit()])])
        elif fam == 5:
            t = ("or", [lit(), lit(), lit()])
        else:
            t = ("or", [("and", [lit(), lit(), lit()]),
                        ("and", [lit(), lit(), lit()])])
        out.append(t)
    return out


def to_expr(t, names: list[str]):
    from repro.db import col
    op = t[0]
    if op == "lit":
        return col(names[t[1]]) == t[2]
    if op == "not":
        return ~to_expr(t[1], names)
    parts = [to_expr(c, names) for c in t[1]]
    return functools.reduce(
        (lambda a, b: a & b) if op == "and" else (lambda a, b: a | b), parts)


def to_mask(t, vals: np.ndarray) -> np.ndarray:
    op = t[0]
    if op == "lit":
        return vals[t[1]] == t[2]
    if op == "not":
        return ~to_mask(t[1], vals)
    parts = [to_mask(c, vals) for c in t[1]]
    return (np.logical_and.reduce(parts) if op == "and"
            else np.logical_or.reduce(parts))


# ------------------------------------------------------------- one chip
def _health_clean(health: dict, where: str) -> None:
    bad = {k: health[k] for k in ("degraded_waves", "fallback_queries",
                                  "wave_retries", "isolated_failures",
                                  "deadline_rejected") if health[k]}
    _check(not bad, f"{where}: the service fell back or failed: {bad}")
    _check(health["breaker"]["state"] == "closed",
           f"{where}: breaker {health['breaker']['state']}")


def _submit_from_callers(svc, exprs: list, callers: int) -> list:
    """Submit ``exprs`` from ``callers`` threads (caller c takes every
    c-th query); returns the futures in query order."""
    futs: list = [None] * len(exprs)
    errors: list = []

    def caller(c: int) -> None:
        try:
            for i in range(c, len(exprs), callers):
                futs[i] = svc.submit(exprs[i])
        except BaseException as e:   # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(c,))
               for c in range(callers)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
        _check(not th.is_alive(), "a caller thread did not finish")
    if errors:
        raise errors[0]
    return futs


def _check_sample(res, pick, want_counts, want_ids, where: str) -> None:
    """A ``query_many`` batch over the sample queries ``pick`` must give
    the NumPy counts and ids."""
    ids = res.all_ids()
    got = np.asarray(res.materialize()[1])[:len(pick)]
    _check(np.array_equal(got, want_counts[pick]),
           f"{where}: sample counts differ from NumPy")
    _check(all(np.array_equal(a, want_ids[int(i)])
               for a, i in zip(ids, pick)),
           f"{where}: sample ids differ from NumPy")


def run_one_chip(seed: int, *, records: int = RECORDS, block: int = BLOCK,
                 spill: int = SPILL, queries: int = QUERIES,
                 sample: int = SAMPLE, callers: int = CALLERS,
                 max_batch: int = MAX_BATCH) -> None:
    import jax

    import repro
    from repro.obs import trace as obs_trace

    names = column_names()
    schema = make_schema()
    t0 = time.perf_counter()
    vals = make_values(seed, records)
    _say(f"data: {records} records x {COLUMNS} words, {schema.num_keys} "
         f"keys; raw records {records * COLUMNS * 4 / 2**30:.3f} GiB as "
         f"int32, resident index {schema.num_keys * records / 8 / 2**30:.3f}"
         f" GiB (made in {time.perf_counter() - t0:.2f}s)")

    path = tempfile.mkdtemp(prefix="chip_smoke-")
    nw = -(-records // 32)
    cap = nw + block // 32 + 1            # capacity buffer: no growth
    try:
        # ---------------------------------------------------------- ingest
        db = repro.BitmapDB(schema, path=path, backend="auto",
                            spill_records=spill, capacity_words=cap)
        _say(f"ingest: index creation backend = {db.indexer.backend}")
        _check(db.indexer.backend == "pallas",
               f"index creation resolved to {db.indexer.backend!r}, "
               "not the Pallas kernels")
        t_first = None
        t0 = time.perf_counter()
        for s in range(0, records, block):
            tb = time.perf_counter()
            db.append({n: vals[i, s:s + block] for i, n in enumerate(names)})
            if t_first is None:
                jax.block_until_ready(db.indexer.view()[0])
                t_first = time.perf_counter() - tb
        jax.block_until_ready(db.indexer.view()[0])
        t_ingest = time.perf_counter() - t0
        segs = db.store.segments
        tail = db.num_records - db.store.durable_records
        _say(f"ingest: {db.num_records} records in {t_ingest:.3f}s wall "
             f"(first block incl. compile {t_first:.3f}s); "
             f"{len(segs)} segments {[s.num_records for s in segs]}, "
             f"WAL tail {tail} records  [smoke timing]")
        _check(db.num_records == records, "record count after ingest")
        _check(len(segs) >= 2 and tail > 0,
               "ingest must leave >= 2 segments and a WAL tail")

        # ----------------------------------------------------------- serve
        trees = make_queries(seed + 1, queries)
        exprs = [to_expr(t, names) for t in trees]
        pick = np.random.default_rng(seed + 2).choice(
            queries, size=min(sample, queries), replace=False)
        svc = db.serve(max_batch=max_batch)
        t0 = time.perf_counter()
        warm = svc.warmup(exprs)
        t_warm = time.perf_counter() - t0
        _say(f"serve: warmup {warm} dispatches in {t_warm:.3f}s "
             f"(compile)  [smoke timing]")
        tracer = obs_trace.install(obs_trace.Tracer(capacity=1 << 20))
        try:
            t0 = time.perf_counter()
            futs = _submit_from_callers(svc, exprs, callers)
            _check(svc.drain(timeout=900), "serve: drain timed out")
            t_serve = time.perf_counter() - t0
        finally:
            obs_trace.uninstall(tracer)
        m = svc.metrics()
        _say(f"serve: {queries} queries from {callers} callers in "
             f"{t_serve:.3f}s wall, {m.batches} waves (mean "
             f"{m.batch_mean:.1f}, max {m.batch_max}), p50 "
             f"{m.latency_p50_ms:.3f}ms p99 {m.latency_p99_ms:.3f}ms  "
             f"[smoke timing]")
        _health_clean(svc.health(), "serve")
        used: dict = {}
        for sp in tracer.spans():
            if sp.name == "bucket.dispatch":
                k = (tuple(sp.attrs["shape"]), sp.attrs["backend"])
                n, q = used.get(k, (0, 0))
                used[k] = (n + 1, q + sp.attrs["q"])
        for (shape, be), (n, q) in sorted(used.items()):
            _say(f"serve: bucket (G,P,L)={shape} -> auto chose {be!r} "
                 f"({n} dispatches, {q} queries)")
        backends_used = {be for _, be in used}
        _check(bool(used), "serve: no bucket was dispatched")
        _check("ref" not in backends_used,
               "serve: a bucket ran on the ref backend, not a kernel")

        counts = np.array([f.count for f in futs], np.int64)
        sample_ids = {int(i): futs[i].ids for i in pick}
        del futs
        t0 = time.perf_counter()
        want_counts = np.empty(queries, np.int64)
        want_ids = {}
        for i, t in enumerate(trees):
            mask = to_mask(t, vals)
            want_counts[i] = np.count_nonzero(mask)
            if i in sample_ids:
                want_ids[i] = np.flatnonzero(mask)
        _say(f"reference: NumPy evaluated {queries} queries in "
             f"{time.perf_counter() - t0:.3f}s; mean count "
             f"{want_counts.mean():.1f}, {np.count_nonzero(want_counts)} "
             "non-empty")
        bad = np.flatnonzero(counts != want_counts)
        _check(bad.size == 0,
               f"serve: {bad.size} counts differ from NumPy, e.g. query "
               f"{bad[:1]}: {counts[bad[:1]]} vs {want_counts[bad[:1]]}")
        for i in pick:
            _check(np.array_equal(sample_ids[int(i)], want_ids[int(i)]),
                   f"serve: ids of query {i} differ from NumPy")
        _say(f"serve: {queries} counts and {len(pick)} id sets equal NumPy")

        sample_exprs = [exprs[i] for i in pick]
        for be in ("pallas", "bulk"):
            res = db.query_many(sample_exprs, backend=be)
            _check_sample(res, pick, want_counts, want_ids, f"backend {be}")
            _say(f"kernels: backend {be!r} answers the sample equal to "
                 "NumPy")

        stats = jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            _say(f"device: peak {stats['peak_bytes_in_use'] / 2**30:.3f} "
                 "GiB in use")

        # --------------------------------------------------------- recover
        svc.close()
        db.store.close()
        segs = db.store.segments
        tail = db.num_records - db.store.durable_records
        _check(len(segs) >= 2 and tail > 0,
               "at close: need >= 2 segments and a WAL tail to recover")
        del svc, db, res
        gc.collect()
        t0 = time.perf_counter()
        db2 = repro.open(path, spill_records=spill, capacity_words=cap)
        jax.block_until_ready(db2.indexer.view()[0])
        t_open = time.perf_counter() - t0
        _check(db2.num_records == records, "record count after recovery")
        _check_sample(db2.query_many(sample_exprs), pick, want_counts,
                      want_ids, "recover")
        _say(f"recover: repro.open replayed {len(segs)} segments + "
             f"{tail} WAL records in {t_open:.3f}s; {len(pick)} sample "
             "queries equal NumPy  [smoke timing]")
    finally:
        shutil.rmtree(path, ignore_errors=True)


# ----------------------------------------------------------- four chips
def run_four_chips(seed: int, *, records: int = RECORDS, block: int = BLOCK,
                   build_blocks: int = 8, queries: int = 256,
                   sample: int = 32) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro
    from repro.engine import backends
    from repro.engine.runtime import multicore_create_index
    from repro.fabric import FabricClient, ShardMap

    devs = jax.devices()[:4]
    names = column_names()
    schema = make_schema()
    keys = jnp.arange(schema.num_keys, dtype=jnp.int32)

    # (a) the shard_map build on a 4-chip mesh vs the one-device build
    vals = make_values(seed, build_blocks * block)
    enc = schema.encode({n: vals[i] for i, n in enumerate(names)})
    enc = enc.reshape(build_blocks, block, COLUMNS)
    mesh = Mesh(np.asarray(devs), ("data",))
    rec = jax.device_put(enc, NamedSharding(mesh, P("data", None, None)))
    t0 = time.perf_counter()
    out = multicore_create_index(rec, keys, mesh, backend="auto")
    jax.block_until_ready(out)
    t_mesh = time.perf_counter() - t0
    placed = sorted(d.id for d in out.sharding.device_set)
    one = jax.jit(backends.get_backend("auto").create_index)
    want = np.stack([np.asarray(one(jax.device_put(enc[b], devs[0]),
                                    jax.device_put(keys, devs[0])))
                     for b in range(build_blocks)])
    got = np.asarray(out)
    _check(np.array_equal(got, want),
           "mesh build differs from the one-device build")
    for k in np.random.default_rng(seed).choice(schema.num_keys, 8,
                                                replace=False):
        c, v = divmod(int(k), VALUES)
        bits = np.packbits(vals[c, :block] == v, bitorder="little")
        _check(np.array_equal(got[0, k], bits.view(np.uint32)),
               f"mesh build: key row {k} differs from NumPy")
    _say(f"mesh: {build_blocks} x {block} records indexed over devices "
         f"{placed} in {t_mesh:.3f}s (incl. compile), bit-identical to "
         f"the one-device build  [smoke timing]")
    del rec, out, got, want, enc

    # (b) FabricClient.local over four hash shards, one chip each
    vals = make_values(seed + 3, records)
    enc = schema.encode({n: vals[i] for i, n in enumerate(names)})
    sm = ShardMap.hashed(schema, names[0], 4, seed=seed)
    parts = {s: (r, g) for s, r, g in sm.partition(enc)}
    _check(sorted(parts) == [0, 1, 2, 3], "every shard must own records")
    dbs, gids = [], []
    t0 = time.perf_counter()
    for s in range(4):
        local, g = parts[s]
        db = repro.BitmapDB(schema, backend="auto", device=devs[s],
                            capacity_words=-(-len(g) // 32) + block // 32 + 1)
        for lo in range(0, len(g), block):
            db.append_encoded(local[lo:lo + block])
        dbs.append(db)
        gids.append(g)
    for db in dbs:
        jax.block_until_ready(db.indexer.view()[0])
    t_ingest = time.perf_counter() - t0
    placement = [sorted(d.id for d in db.indexer.view()[0].devices())
                 for db in dbs]
    for s, (db, ids) in enumerate(zip(dbs, placement)):
        _say(f"fabric: shard {s} holds {db.num_records} records on "
             f"device {ids}")
    _check(placement == [[d.id] for d in devs],
           f"shards must sit one per device, got {placement}")
    _say(f"fabric: ingest {records} records into 4 shards in "
         f"{t_ingest:.3f}s wall  [smoke timing]")

    trees = make_queries(seed + 1, queries)
    exprs = [to_expr(t, names) for t in trees]
    pick = set(np.random.default_rng(seed + 2).choice(
        queries, size=min(sample, queries), replace=False).tolist())
    with FabricClient.local(dbs, sm, schema=schema, gids=gids) as fc:
        t0 = time.perf_counter()
        futs = [fc.submit(e, count_only=i not in pick)
                for i, e in enumerate(exprs)]
        _check(fc.drain(timeout=900), "fabric: drain timed out")
        t_serve = time.perf_counter() - t0
        for s, sh in enumerate(fc.metrics()["shards"]):
            _health_clean(sh["health"], f"fabric shard {s}")
        for i, (t, f) in enumerate(zip(trees, futs)):
            mask = to_mask(t, vals)
            _check(f.count == np.count_nonzero(mask),
                   f"fabric: count of query {i} differs from NumPy")
            if i in pick:
                _check(np.array_equal(f.ids, np.flatnonzero(mask)),
                       f"fabric: ids of query {i} differ from NumPy")
    _say(f"fabric: {queries} counts and {len(pick)} id sets over 4 "
         f"shards equal NumPy; served in {t_serve:.3f}s wall  "
         "[smoke timing]")


# ------------------------------------------------------------------ main
def _platform(chips: int):
    """The devices, or SmokeFailure when JAX finds no TPU (or too few)."""
    import importlib.metadata

    import jax
    import jaxlib

    devs = jax.devices()
    _check(devs[0].platform == "tpu",
           f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s); "
           "this smoke runs only on the chip")
    _check(len(devs) >= chips,
           f"--chips {chips} needs {chips} TPU chips, JAX found {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    _say(f"device: {devs[0].device_kind} x {len(devs)}; jax "
         f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths, on four chips")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        devs = _platform(args.chips)
        from repro import jaxcache
        _say(f"compile cache: {jaxcache.enable()}")
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
