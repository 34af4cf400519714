"""Chaos smoke driver (CI): seeded fault schedules against the full
ingest + serve + maintenance stack, clean run vs faulted run.

For each fixed seed this runs the same workload twice — once clean, once
under ``FaultPlan.random(seed, profile="all")`` — and requires:

  * bit-identical per-wave query counts (retries / fallback / repair are
    invisible in the data);
  * bit-identical recovered state after reopening both stores from disk;
  * nothing left quarantined once the schedule drains.

A second, **network** phase runs the same idea one layer up: a sharded
fabric (loopback transports, so the ``rpc.send``/``rpc.recv`` seams fire
without sockets) ingests and queries under
``FaultPlan.random(seed, profile="network")`` — messages dropped,
duplicated, delayed, and reordered — and must end with every
acknowledged append present exactly once and every query count equal to
the clean single-node reference (zero acked-write loss, zero wrong
bits).

Artifacts land in ``results/chaos/``: the fault schedule + fired-event
report (``seed<N>.faults.json``, ``seed<N>.network.faults.json``) and
the end-of-run service health (``seed<N>.health.json``) — on a CI
failure these are what you read.

Usage: python benchmarks/chaos.py [seed ...]      (default: 11 23 47)
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, "src")

SEEDS = (11, 23, 47)
OUT_DIR = os.path.join("results", "chaos")
M, BLOCK, WORDS, N_BLOCKS = 12, 96, 3, 8
APPEND_RETRIES = 12


def _blocks(seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, M, (BLOCK, WORDS), dtype=np.int32)
            for _ in range(N_BLOCKS)]


def _run(root: str, plan):
    """One ingest+serve+maintenance workload; returns per-wave counts and
    the final service health dict."""
    from repro.db import BitmapDB
    from repro.engine.planner import key
    from repro.fault import FaultInjector

    db = BitmapDB(num_keys=M, path=root, spill_records=256)
    svc = db.serve(background=True, max_delay_ms=1.0, wave_retries=3,
                   breaker_cooldown_s=0.05, idle_after_ms=50.0)
    inj = FaultInjector(plan).install() if plan is not None else None
    try:
        waves = []
        for block in _blocks(7):
            for _ in range(APPEND_RETRIES):     # acked-or-retried ingest
                try:
                    db.append_encoded(block)
                    break
                except OSError:
                    continue
            else:
                raise RuntimeError("append never acknowledged")
            waves.append([svc.submit(key(i)).count for i in range(M)])
    finally:
        if inj is not None:
            inj.uninstall()
    if not svc._maint_ex.flush(30):
        raise RuntimeError("maintenance flush timed out")
    health = svc.health()
    svc.close()
    return waves, health, inj


def _reopened_counts(root: str):
    from repro.db.session import open_db
    from repro.engine.planner import key

    db = open_db(root, num_keys=M)
    try:
        return db.num_records, [db.query(key(i)).count for i in range(M)]
    finally:
        db.store.close()


def run_seed(seed: int) -> list[str]:
    """Returns a list of failure strings (empty = pass) and writes the
    artifacts for this seed."""
    from repro.fault import FaultPlan

    from repro.obs import export as obs_export
    from repro.obs import trace as obs_trace

    plan = FaultPlan.random(seed, profile="all")
    with tempfile.TemporaryDirectory() as tmp:
        clean_waves, _, _ = _run(os.path.join(tmp, "clean"), None)
        # trace the faulted run: every fired fault lands as a
        # zero-duration fault.<kind> event inside whatever span it
        # interrupted, so the merged JSONL artifact shows WHERE in the
        # serve/maintenance/store chain each injection hit
        tracer = obs_trace.Tracer(capacity=1 << 18)
        obs_trace.install(tracer)
        try:
            chaos_waves, health, inj = _run(os.path.join(tmp, "chaos"),
                                            plan)
        finally:
            obs_trace.uninstall(tracer)
        n_a, counts_a = _reopened_counts(os.path.join(tmp, "clean"))
        n_b, counts_b = _reopened_counts(os.path.join(tmp, "chaos"))

    obs_export.write_jsonl(
        tracer.spans(), os.path.join(OUT_DIR, f"seed{seed}.trace.jsonl"))
    with open(os.path.join(OUT_DIR, f"seed{seed}.faults.json"), "w") as f:
        f.write(inj.report_json())
    with open(os.path.join(OUT_DIR, f"seed{seed}.health.json"), "w") as f:
        json.dump(health, f, indent=2, sort_keys=True, default=repr)
        f.write("\n")

    failures = []
    if chaos_waves != clean_waves:
        failures.append("served bits differ from the clean run")
    if (n_a, counts_a) != (n_b, counts_b):
        failures.append(f"recovered state differs: {n_a} vs {n_b} records")
    if health["store"] and health["store"]["quarantined"]:
        failures.append(f"segments left quarantined: "
                        f"{health['store']['quarantined']}")
    return failures


def run_network_seed(seed: int) -> list[str]:
    """The fabric phase: sharded appends + queries under the network
    fault profile.  Every ``append_encoded`` that RETURNS is an
    acknowledged write — the pass condition is that all of them (and
    nothing else) are present at the end, with query counts identical
    to a clean single-node session over the same records."""
    from repro.db import BitmapDB
    from repro.engine.planner import key
    from repro.fabric.client import FabricClient
    from repro.fabric.shardmap import ShardMap
    from repro.fault import FaultInjector, FaultPlan

    plan = FaultPlan.random(seed, profile="network", n_faults=24,
                            max_occurrence=48, max_stall_s=0.002)
    blocks = _blocks(13)
    # clean single-node truth
    ref = BitmapDB(num_keys=M)
    for b in blocks:
        ref.append_encoded(b)
    truth = [ref.query(key(i)).count for i in range(M)]

    # schemaless session: every column shares the key range, so a key
    # predicate is NOT column-0-only — cardinality=0 disables pruning
    # (routing still hashes column 0) and every query fans out
    sm = ShardMap(num_shards=3, strategy="hash", column_index=0,
                  base=0, cardinality=0, seed=seed)
    fc = FabricClient.local(
        [BitmapDB(num_keys=M) for _ in range(3)], sm,
        max_delay_ms=1.0, request_timeout_s=0.5, request_retries=10,
        append_retries=12)
    failures = []
    acked = 0
    inj = FaultInjector(plan).install()
    try:
        for b in blocks:
            acked = fc.append_encoded(b)      # returns only when acked
            mid = [fc.submit(key(i)).count for i in range(M)]
            if any(c > t for c, t in zip(mid, truth)):
                failures.append("mid-run count exceeds the reference")
        final = [fc.submit(key(i)).count for i in range(M)]
        stored = sum(p["num_records"] for p in fc.info())
    finally:
        inj.uninstall()
        fc.close()

    with open(os.path.join(OUT_DIR,
                           f"seed{seed}.network.faults.json"), "w") as f:
        f.write(inj.report_json())
    if acked != len(blocks) * BLOCK:
        failures.append(f"acked {len(blocks) * BLOCK} records, fabric "
                        f"reports {acked}")
    if stored != len(blocks) * BLOCK:
        failures.append(f"shards hold {stored} records, {acked} were "
                        f"acknowledged (lost or double-applied write)")
    if final != truth:
        failures.append("fabric counts differ from the clean "
                        "single-node reference (acked write lost or "
                        "double-applied)")
    return failures


def main(*argv: str) -> int:
    seeds = tuple(int(a) for a in argv) or SEEDS
    os.makedirs(OUT_DIR, exist_ok=True)
    bad = 0
    for seed in seeds:
        failures = run_seed(seed)
        status = "FAIL" if failures else "ok"
        print(f"chaos seed={seed}: {status}"
              + "".join(f"\n  - {f}" for f in failures), flush=True)
        bad += bool(failures)
    for seed in seeds:
        failures = run_network_seed(seed)
        status = "FAIL" if failures else "ok"
        print(f"chaos seed={seed} profile=network: {status}"
              + "".join(f"\n  - {f}" for f in failures), flush=True)
        bad += bool(failures)
    print(f"chaos smoke: {len(seeds) - bad}/{len(seeds)} seeds clean "
          f"(artifacts in {OUT_DIR}/)")
    return 1 if bad else 0


if __name__ == "__main__":
    from repro import jaxcache
    jaxcache.enable()
    sys.exit(main(*sys.argv[1:]))
