"""Quickstart: the paper's Fig. 1/Fig. 3 flow through the `repro.db` facade.

A BIC core turns records into a key-major bitmap index so that
multi-dimensional queries become streaming bitwise passes.  `repro.db`
wraps that silicon-shaped core in a database port: a `Schema` names the
key rows, `col(...)` expressions compile to fused bitmap passes, and one
`BitmapDB` session owns ingest, durability, and query serving.

Run:  PYTHONPATH=src python examples/quickstart.py

Hacking on the tree?  `PYTHONPATH=src python -m repro.analysis` runs the
domain lint (lock hierarchy, fault-seam coverage, jit hygiene,
span/metric taxonomy, wire exhaustiveness — see the "Static analysis"
section of ARCHITECTURE.md); CI fails on any unbaselined finding, and
`REPRO_LOCK_WITNESS=1 pytest` cross-checks the lock hierarchy at
runtime.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, "src")

import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.db import col  # noqa: E402
from repro.engine import key  # noqa: E402

DOMAINS = ["web", "code", "math", "news"]
LANGS = ["en", "de", "ja"]
TEMP_EDGES = [-10.0, 0.0, 10.0, 20.0, 30.0, 45.0]


def make_rows(rng, n):
    return {
        "domain": [DOMAINS[i] for i in rng.integers(0, len(DOMAINS), n)],
        "lang": [LANGS[i] for i in rng.integers(0, len(LANGS), n)],
        "temp": rng.uniform(-10, 45, n).round(2).tolist(),
        "flagged": [bool(b) for b in rng.random(n) < 0.1],
    }


def brute(rows, i):
    """The quickstart query, evaluated by brute force per record."""
    return (rows["domain"][i] in ("code", "math")
            and rows["lang"][i] == "en"
            and 10.0 <= rows["temp"][i]
            and not rows["flagged"][i])


def main():
    rng = np.random.default_rng(0)
    schema = repro.Schema([
        repro.Column.categorical("domain", DOMAINS),
        repro.Column.categorical("lang", LANGS),
        repro.Column.binned("temp", edges=TEMP_EDGES),
        repro.Column.categorical("flagged", [False, True]),
    ])
    print(schema)

    # ---- ingest: structured rows -> streaming bitmap index -------------
    db = repro.BitmapDB(schema)
    n = 4096
    rows = make_rows(rng, n)
    db.ingest(rows)
    print(f"ingested {db.num_records} records over {db.num_keys} key rows")

    # ---- query: typed expressions compile to fused bitmap passes -------
    q = (col("domain").isin(["code", "math"]) & (col("lang") == "en")
         & (col("temp") >= 10.0) & ~(col("flagged") == True))  # noqa: E712
    res = db.query(q)
    want = [i for i in range(n) if brute(rows, i)]
    assert list(res.ids) == want, "bitmap query must match brute force"
    print(f"query code|math & en & temp>=10 & ~flagged -> {res.count} "
          f"records: {[int(i) for i in res.ids[:8]]} ... "
          "(verified by brute force)")

    # raw integer key rows still work (the engine predicate surface)
    k = schema.key_of("domain", "code")
    res2 = db.query(key(k) & ~key(schema.key_of("flagged", True)))
    print(f"raw predicate key({k}) & ~flagged -> {res2.count} records")

    # ---- stats feed the planner's cheapest-first clause ordering -------
    st = db.stats
    labels = [schema.key_label(i) for i in range(3)]
    print(f"per-key selectivity stats: {labels} -> {st.counts[:3]}")

    # ---- explain: how a query WOULD run, without running it ------------
    # The session serves with backend="auto": a measured cost model picks
    # the cheapest execution backend per dispatch (the fused bulk-bitwise
    # sweep vs the per-pass paths) from a persisted calibration of this
    # host.  explain() surfaces that decision: the lowered pass program,
    # its padded bucket shape, the selectivity estimate, and the
    # per-candidate time estimates behind the backend choice.
    ex = db.explain(q)
    est = {k: f"{v * 1e6:.0f}us" for k, v in ex["decision"]["estimates"]
           .items()} if ex["decision"] else {}
    print(f"explain: bucket_shape={ex['bucket_shape']} "
          f"backend={ex['backend']} est_matches={ex['est_matches']:.0f} "
          f"(actual {res.count}) candidates={est}")

    # ---- durability: spill to a store, crash, recover ------------------
    with tempfile.TemporaryDirectory() as root:
        path = os.path.join(root, "idx")
        durable = repro.BitmapDB(schema, path=path, spill_records=1024)
        cut = n - 500                   # last 500 stay under the threshold
        durable.ingest({k2: v[:cut] for k2, v in rows.items()})
        durable.append({k2: v[cut:] for k2, v in rows.items()})
        segs = len(durable.store.segments)
        wal_blocks = len(durable.store.replay_wal())
        assert wal_blocks, "the final sub-threshold block must be WAL-only"
        # "crash": reopen from disk — manifest + WAL replay, bit-identical
        recovered = repro.open(path)
        assert recovered.num_records == n
        assert list(recovered.query(q).ids) == want
        print(f"recovered {recovered.num_records} records from {segs} "
              f"segments + a {wal_blocks}-block WAL tail; query results "
              "bit-identical")

        # ---- serving: one step function over the bucketed executor ----
        step = recovered.serve_step()
        batch = [q, col("lang") == "de", key(k),
                 col("temp").between(0, 20) & (col("domain") == "web")]
        rows_out, counts = step(batch)
        print(f"served a {len(batch)}-query batch in bucketed dispatches: "
              f"counts={[int(c) for c in counts]}")

        # ---- the service port: micro-batching + standby duty cycle ----
        # submit() from any number of threads returns a future; the
        # scheduler coalesces everything inside the delay window into ONE
        # bucketed dispatch, then duty-cycles into standby when idle —
        # the paper's operating model as an API.
        with recovered.serve(max_delay_ms=2.0, idle_after_ms=10.0) as svc:
            futs = [svc.submit(qq) for qq in batch * 8]   # 32 requests
            svc.drain()
            assert [int(f.count) for f in futs[:4]] == \
                [int(c) for c in counts]
            deadline = time.time() + 5        # idle past the threshold
            while svc.state != "standby" and time.time() < deadline:
                time.sleep(0.01)
            m = svc.metrics()
            print(f"service: {m.served} queries in {m.batches} coalesced "
                  f"batch(es), p50={m.latency_p50_ms:.2f}ms, "
                  f"state={m.state}, active={m.active_joules:.2e}J "
                  f"standby={m.standby_joules:.2e}J")
            assert m.state == "standby", "idle service must clock-gate"

    # ---- the fabric: the same query plane over N shard stores ----------
    # A ShardMap hash-partitions records by their domain key; each shard
    # is a full BitmapDB+BitmapService stack behind a transport (loopback
    # here — `repro.fabric.worker.spawn_shards` runs the identical stack
    # as real processes, see benchmarks/fabric.py).  The FabricClient
    # keeps the submit()/future surface, scatters each query to the
    # shards that can own it, and merges rows bit-identically.
    from repro.db.expr import lower as lower_expr
    from repro.fabric import FabricClient, ShardMap
    sm = ShardMap(num_shards=3, strategy="hash", column_index=0,
                  base=0, cardinality=len(DOMAINS), seed=1)
    with FabricClient.local([repro.BitmapDB(schema) for _ in range(3)],
                            sm) as fc:
        fc.append(rows)
        fut = fc.submit(q)
        assert list(fut.ids) == want, "fabric must merge bit-identically"
        served = [h["served"] for h in fc.metrics()["shards"]]
        owners = sorted(sm.owners(lower_expr(q, schema)))
        print(f"fabric: 3 hash shards served {fut.count} matches "
              f"(per-shard served={served}, query pruned to "
              f"shards {owners})")

    print("quickstart OK")


if __name__ == "__main__":
    from repro import jaxcache
    jaxcache.enable()
    main()
