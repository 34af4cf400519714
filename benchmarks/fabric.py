"""Fabric scaling benchmark — one query plane over N shard PROCESSES.

Measures the distributed read path end to end: ``spawn_shards`` forks
1 / 2 / 4 / 8 real worker processes (each a ``BitmapDB`` +
``BitmapService`` + socket server over its hash-partition of the
records), a ``FabricClient`` ingests one corpus through the exactly-once
append protocol, and a 10k-query storm of owner-pruned predicates is
submitted concurrently and merged.  Three gated claims (benchmarks/
check.py):

  fabric_scaling_ok — read throughput scales: with owner pruning each
      query executes against 1/N of the records on 1 of N processes, so
      aggregate qps at N shards must reach >= 0.7x the core-aware linear
      ideal, ``qps_1 * min(N, cpu_count)``.  On a single-core runner the
      ideal is flat and the gate degenerates to "eight processes cost at
      most 30% over one" (pure fabric overhead); on a multi-core runner
      it demands real parallel speedup.  The per-size counts must also
      be identical — a scaling number over wrong answers is worthless.
  fabric_bitexact  — a mixed fan-out suite (DSL expressions + raw
      predicate trees, rows + counts + ids) through the 8-process fabric
      is bit-identical to one single-node ``BitmapDB`` session over the
      same records.
  fabric_chaos_ok  — a seeded ``network`` fault schedule (drop /
      duplicate / delay / reorder on the rpc seams) loses ZERO
      acknowledged writes: every acked append is durably applied
      (server-side ``info()`` totals) and final counts match a clean
      reference.

Writes/merges its row into BENCH_engine.json (``BENCH_JSON`` env var
overrides), preserving rows from benchmarks/run.py.

One process per chip: each worker imports JAX, and on a TPU host one
process holds the chips, so ``spawn_shards`` refuses to start workers
that would claim a TPU (it decides from ``JAX_PLATFORMS`` and the host's
devices, never by initialising JAX in this parent).  This benchmark
therefore runs where its workers get the CPU — ``JAX_PLATFORMS=cpu`` on a
chip host — and measures the multi-process fabric, not the chip.  Shards
on chips run in one process, each ``BitmapDB`` pinned to its own device
(``chip_smoke.py --chips 4``).

Usage: python benchmarks/fabric.py [--sizes 1,2,4,8] [--queries 10000]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.db import BitmapDB, Column, Schema, col  # noqa: E402
from repro.engine.planner import key  # noqa: E402
from repro.fabric.client import FabricClient  # noqa: E402
from repro.fabric.shardmap import ShardMap  # noqa: E402
from repro.fabric.worker import spawn_shards  # noqa: E402

CARD = 64                     # values per column -> 256 key rows
NCOLS = 4
SEED = 7


def _schema() -> Schema:
    return Schema([Column.categorical(c, list(range(CARD)))
                   for c in ("a", "b", "c", "d")])


def _records(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(CARD * j, CARD * (j + 1), n,
                                  dtype=np.int32)
                     for j in range(NCOLS)], axis=1)


def _pruned_queries(nq: int, seed: int) -> list:
    """Owner-pruned 3-pass predicates: the column-0 literal pins the
    owning shard, the other two keep per-query execution non-trivial."""
    rng = np.random.default_rng(seed)
    return [key(int(rng.integers(0, CARD)))
            & key(int(rng.integers(CARD, 2 * CARD)))
            & ~key(int(rng.integers(2 * CARD, 3 * CARD)))
            for _ in range(nq)]


def _fanout_queries(nq: int, seed: int) -> list:
    """Un-prunable mixed suite (DSL + raw trees): every query consults
    every shard and the client OR-splices rows back together."""
    rng = np.random.default_rng(seed)

    def v(j):
        return int(rng.integers(0, CARD))

    out = []
    for i in range(nq):
        fam = i % 5
        if fam == 0:
            out.append(col("b") == v(1))
        elif fam == 1:
            out.append(col("b").isin([v(1), v(1)]) & (col("c") == v(2)))
        elif fam == 2:
            out.append((col("c") == v(2)) | (col("d") == v(3)))
        elif fam == 3:
            out.append(key(CARD + v(1)) & ~key(2 * CARD + v(2)))
        else:
            out.append((col("a") == v(0)) | (col("b") == v(1)))
    return out


def _shardmap(num_shards: int) -> ShardMap:
    return ShardMap(num_shards=num_shards, strategy="hash",
                    column_index=0, base=0, cardinality=CARD, seed=SEED)


def _storm(fc: FabricClient, queries: list, *, count_only: bool = True):
    t0 = time.perf_counter()
    futs = fc.submit_many(queries, count_only=count_only)
    fc.drain()
    counts = [f.count for f in futs]
    return time.perf_counter() - t0, counts, futs


def fabric_scaling(sizes: tuple[int, ...], n: int, nq: int,
                   artifact_dir: str | None = None) -> dict:
    recs = _records(n, seed=3)
    storm_qs = _pruned_queries(nq, seed=77)
    ident_qs = _fanout_queries(512, seed=78)

    # single-node reference for the bit-identity phase
    ref = BitmapDB(_schema())
    ref.append_encoded(recs)
    ref_res = ref.query_many(ident_qs).materialize()
    ref_rows = np.asarray(ref_res[0])
    ref_counts = [int(c) for c in ref_res[1]]
    ref_ids = [np.flatnonzero(np.unpackbits(
        ref_rows[i].view(np.uint8), bitorder="little")[:n])
        for i in range(len(ident_qs))]
    del ref, ref_res                  # keep worker processes out of swap

    qps: dict[int, float] = {}
    counts0: list[int] | None = None
    counts_ok = True
    bitexact = False
    for num_shards in sizes:
        t0 = time.perf_counter()
        with spawn_shards(num_shards, schema=_schema(),
                          service_config={"max_batch": 512},
                          artifact_dir=(artifact_dir
                                        if num_shards == max(sizes)
                                        else None)) as fleet:
            t_spawn = time.perf_counter() - t0
            fc = FabricClient.connect(fleet.addresses,
                                      _shardmap(num_shards),
                                      schema=_schema(), max_batch=2048)
            t0 = time.perf_counter()
            for i in range(0, n, 131072):
                fc.append_encoded(recs[i:i + 131072])
            t_load = time.perf_counter() - t0
            _storm(fc, storm_qs[:2048])          # warm shapes + plans
            dt, counts, _ = _storm(fc, storm_qs)
            qps[num_shards] = nq / dt
            if counts0 is None:
                counts0 = counts
            elif counts != counts0:
                counts_ok = False
            print(f"# fabric_scaling shards={num_shards} "
                  f"spawn={t_spawn:.1f}s load={t_load:.1f}s "
                  f"storm={dt:.2f}s qps={nq / dt:.0f}", flush=True)
            if num_shards == max(sizes):
                # bit-identity: fan-out suite, rows + counts + ids
                futs = fc.submit_many(ident_qs)
                fc.drain()
                bitexact = True
                for i, f in enumerate(futs):
                    row = np.asarray(f.rows)[:ref_rows.shape[1]]
                    bitexact = (bitexact
                                and row.shape == ref_rows[i].shape
                                and bool(np.array_equal(row, ref_rows[i]))
                                and int(f.count) == ref_counts[i]
                                and bool(np.array_equal(f.ids,
                                                        ref_ids[i])))
                stats = fc.metrics()
            fc.close()

    cores = os.cpu_count() or 1
    lo, hi = min(sizes), max(sizes)
    ideal = qps[lo] * min(hi, cores)
    eff = qps[hi] / ideal
    scaling_ok = eff >= 0.7 and counts_ok
    return {"qps": qps, "eff": eff, "cores": cores,
            "scaling_ok": scaling_ok, "bitexact": bitexact,
            "counts_ok": counts_ok, "served": stats.get("served"),
            "storm_s": nq / qps[hi]}


def fabric_chaos(seed: int = 23) -> dict:
    """Loopback fabric under a seeded network fault schedule: zero
    acknowledged-write loss, final counts equal a clean reference."""
    from repro.fault import FaultInjector, FaultPlan

    m, nblk, blk = 96, 6, 64
    plan = FaultPlan.random(seed, profile="network", n_faults=16,
                            max_occurrence=24, max_stall_s=0.001)
    rng = np.random.default_rng(seed * 11 + 1)
    blocks = [rng.integers(0, m, (blk, 3)).astype(np.int32)
              for _ in range(nblk)]
    ref = BitmapDB(num_keys=m)
    for b in blocks:
        ref.append_encoded(b)
    truth = [ref.query(key(i)).count for i in range(m)]

    # schemaless session: every column shares the key range, so pruning
    # must stay off (cardinality=0); routing still hashes column 0
    sm = ShardMap(num_shards=2, strategy="hash", column_index=0,
                  base=0, cardinality=0, seed=seed)
    fc = FabricClient.local([BitmapDB(num_keys=m) for _ in range(2)], sm,
                            max_delay_ms=1.0, request_timeout_s=0.5,
                            request_retries=10, append_retries=12)
    acked = 0
    fired = 0
    try:
        with FaultInjector(plan) as inj:
            for b in blocks:
                acked = fc.append_encoded(b)   # returns the acked total
            futs = fc.submit_many([key(i) for i in range(m)],
                                  count_only=True)
            fc.drain()
            final = [f.count for f in futs]
            fired = len(inj.fired())
        stored = sum(p["num_records"] for p in fc.info())
    finally:
        fc.close()
    ok = (acked == nblk * blk and stored == acked and final == truth)
    return {"acked": acked, "stored": stored, "fired": fired,
            "counts_match": final == truth, "ok": ok}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="1,2,4,8")
    ap.add_argument("--queries", type=int, default=10_000)
    ap.add_argument("--records", type=int, default=1 << 20)
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="write per-shard trace/health/metrics JSON for "
                         "the largest fleet (CI fabric-smoke uploads)")
    a = ap.parse_args(argv)
    sizes = tuple(int(s) for s in a.sizes.split(","))

    print("name,us_per_call,derived")
    sc = fabric_scaling(sizes, a.records, a.queries, a.artifacts)
    ch = fabric_chaos()
    qps_s = " ".join(f"qps{k}={v:.0f}" for k, v in sorted(sc["qps"].items()))
    us = sc["storm_s"] / a.queries * 1e6
    derived = (f"{qps_s} eff_vs_linear={sc['eff']:.2f} "
               f"cores={sc['cores']} shards={max(sizes)} "
               f"queries={a.queries} records={a.records} "
               f"chaos_acked={ch['acked']} chaos_stored={ch['stored']} "
               f"chaos_faults={ch['fired']} "
               f"fabric_scaling_ok={sc['scaling_ok']} "
               f"fabric_bitexact={sc['bitexact']} "
               f"fabric_chaos_ok={ch['ok']}")
    print(f"fabric_scaling,{us:.2f},{derived}", flush=True)

    path = os.environ.get("BENCH_JSON", "BENCH_engine.json")
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["fabric_scaling"] = {"us_per_call": us, "derived": derived}
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    print(f"# merged fabric_scaling into {path} ({len(data)} rows)")
    return 0 if (sc["scaling_ok"] and sc["bitexact"] and ch["ok"]) else 1


if __name__ == "__main__":
    from repro import jaxcache
    jaxcache.enable()
    sys.exit(main())
