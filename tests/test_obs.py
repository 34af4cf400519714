"""Acceptance suite for the `repro.obs` observability layer.

Covers the span tracer (explicit clock, ambient nesting, cross-thread
parent handoff, bounded ring), the typed metric registry (counters /
gauges / histograms / reservoirs, registry grafting, Prometheus text),
the energy ledger (phase charging, per-query attribution, the two
reconciliation invariants), and the integration contract: a traced
1k-query / 8-caller storm through a live `BitmapService` yields a trace
that reconstructs every query's full span chain (admission -> queue ->
serve, joined to its wave's coalesce subtree), with per-query pJ that
sums back to the scheduler's energy total; `metrics()` / `health()` /
`cache_stats()` stay safe to call from reader threads mid-storm; fired
faults land as events inside the span they interrupted; and the
disabled path records nothing.
"""
import itertools
import json
import threading

import numpy as np
import pytest

from repro.db import BitmapDB, Column, Schema, col
from repro.obs import energy as obs_energy
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


# ----------------------------------------------------------------- fixtures
@pytest.fixture
def fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    clock.advance = lambda dt: t.__setitem__(0, t[0] + dt)
    return clock


@pytest.fixture
def installed_tracer():
    tracer = obs_trace.Tracer(capacity=1 << 17)
    obs_trace.install(tracer)
    try:
        yield tracer
    finally:
        obs_trace.uninstall(tracer)


def _schema(m: int = 16) -> Schema:
    half = m // 2
    return Schema([Column.categorical("a", list(range(half))),
                   Column.categorical("b", list(range(half, m)))])


def _mk_db(n: int = 2048, m: int = 16, seed: int = 0) -> BitmapDB:
    half = m // 2
    rng = np.random.default_rng(seed)
    enc = np.stack([rng.integers(0, half, n, dtype=np.int32),
                    rng.integers(half, m, n, dtype=np.int32)], axis=1)
    db = BitmapDB(_schema(m), backend="ref")
    db.append_encoded(enc)
    return db


def _mixed_queries(rng, m: int, count: int) -> list:
    half = m // 2
    qs = []
    for i in range(count):
        if i % 3 == 0:
            qs.append(col("a") == int(rng.integers(0, half)))
        elif i % 3 == 1:
            qs.append((col("a") == int(rng.integers(0, half)))
                      | (col("b") == int(rng.integers(half, m))))
        else:
            qs.append((col("a") == int(rng.integers(0, half)))
                      & ~(col("b") == int(rng.integers(half, m))))
    return qs


def _storm(svc, queries, callers: int = 8):
    futs = [None] * len(queries)
    errs = []

    def caller(lane):
        try:
            for i in range(lane, len(queries), callers):
                futs[i] = svc.submit(queries[i])
        except BaseException as e:              # noqa: BLE001 — reported
            errs.append(e)

    threads = [threading.Thread(target=caller, args=(c,))
               for c in range(callers)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert svc.drain(timeout=60)
    assert not errs
    return futs


# ------------------------------------------------------------------- tracer
def test_span_nesting_and_explicit_parents(fake_clock):
    tr = obs_trace.Tracer(fake_clock)
    with tr.span("outer", wave=3) as outer:
        fake_clock.advance(1.0)
        with tr.span("inner") as inner:
            fake_clock.advance(0.5)
        # cross-thread style: explicit (trace, span) tuple parent
        handed = tr.record("handoff", parent=outer.context,
                           t0=0.25, t1=0.75)
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"outer", "inner", "handoff"}
    assert spans["inner"].parent_id == outer.span_id
    assert spans["inner"].trace_id == outer.trace_id
    assert handed.parent_id == outer.span_id
    assert spans["outer"].duration_s == pytest.approx(1.5)
    assert spans["inner"].duration_s == pytest.approx(0.5)
    assert spans["outer"].attrs["wave"] == 3
    # roots have parent 0; nesting popped back out
    assert spans["outer"].parent_id == 0
    assert tr.current() is None


def test_span_error_annotation(fake_clock):
    tr = obs_trace.Tracer(fake_clock)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("nope")
    (sp,) = tr.spans()
    assert "ValueError" in sp.attrs["error"]


def test_ring_bound_and_dropped(fake_clock):
    tr = obs_trace.Tracer(fake_clock, capacity=8)
    for i in range(20):
        tr.record(f"s{i}", t0=0.0, t1=1.0)
    assert len(tr) == 8
    assert tr.dropped == 12
    assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]
    assert tr.drain() and len(tr) == 0


def test_install_ownership_and_maybe_span():
    assert obs_trace.TRACER is None
    assert obs_trace.current_context() is None
    cm = obs_trace.maybe_span("store.scrub")
    with cm as sp:
        assert sp is None                       # shared no-op when off
    a, b = obs_trace.Tracer(), obs_trace.Tracer()
    obs_trace.install(a)
    try:
        obs_trace.install(a)                    # idempotent re-install
        with pytest.raises(RuntimeError):
            obs_trace.install(b)
        with pytest.raises(RuntimeError):
            obs_trace.uninstall(b)
        with obs_trace.maybe_span("x") as sp:
            assert sp is not None
            assert obs_trace.current_context() == sp.context
    finally:
        obs_trace.uninstall(a)
    obs_trace.uninstall()                       # idempotent when off


def test_sink_receives_span_dicts(fake_clock):
    lines = []
    tr = obs_trace.Tracer(fake_clock, sink=lines.append)
    tr.record("a", t0=0.0, t1=2.0, k="v")
    assert lines == [tr.spans()[0].to_dict()]
    assert lines[0]["dur_ms"] == pytest.approx(2000.0)
    assert lines[0]["attrs"] == {"k": "v"}


# ------------------------------------------------------------------ metrics
def test_counter_gauge_histogram():
    reg = obs_metrics.Registry()
    c = reg.counter("served_total")
    c.inc()
    c.add(4)
    assert c.value == 5
    assert reg.counter("served_total") is c     # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("served_total")               # kind mismatch
    g = reg.gauge("depth")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    h = reg.histogram("lat", (1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.count == 4
    snap = h.snapshot()
    assert snap["overflow"] == 1
    assert [n for _, n in snap["buckets"]] == [1, 1, 1]
    assert 0.0 <= h.quantile(0.5) <= 100.0


def test_reservoir_bounded_deterministic_exact_small():
    r = obs_metrics.Reservoir("lat", capacity=64, seed=3)
    for v in range(50):
        r.observe(float(v))
    # below capacity: lifetime-exact percentiles
    assert r.percentile(0) == 0.0
    assert r.percentile(100) == 49.0
    assert r.percentile(50) == pytest.approx(24.5)
    for v in range(50, 100_000):
        r.observe(float(v))
    assert len(r.values()) == 64                # memory stays flat
    assert r.count == 100_000
    r2 = obs_metrics.Reservoir("lat", capacity=64, seed=3)
    for v in range(100_000):
        r2.observe(float(v))
    assert r.values() == r2.values()            # seeded: deterministic


def test_registry_attach_collect_prometheus():
    root, child = obs_metrics.Registry(), obs_metrics.Registry()
    child.counter("repairs_total").add(2)
    root.counter("served_total").inc()
    root.attach("store", child)
    root.attach("store", child)                 # re-attach same: no-op
    with pytest.raises(ValueError):
        root.attach("store", obs_metrics.Registry())
    names = dict(root.collect())
    assert {"served_total", "store_repairs_total"} <= set(names)
    text = obs_export.prometheus_text(root, prefix="repro")
    assert "repro_served_total 1" in text
    assert "repro_store_repairs_total 2" in text
    snap = root.snapshot()
    assert snap["store_repairs_total"] == 2


def test_prometheus_histogram_and_reservoir_exposition():
    reg = obs_metrics.Registry()
    h = reg.histogram("lat_ms", (1.0, 10.0))
    h.observe(0.5)
    h.observe(99.0)
    r = reg.reservoir("rt", capacity=16)
    r.observe(4.0)
    text = obs_export.prometheus_text(reg)
    assert 'repro_lat_ms_bucket{le="+Inf"} 2' in text
    assert "repro_lat_ms_count 2" in text
    assert 'quantile="0.5"' in text


def test_write_jsonl(tmp_path, fake_clock):
    tr = obs_trace.Tracer(fake_clock)
    tr.record("a", t0=0.0, t1=1.0)
    tr.record("b", t0=1.0, t1=2.0)
    path = tmp_path / "out" / "trace.jsonl"
    assert obs_export.write_jsonl(tr.spans(), str(path)) == 2
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["a", "b"]


# ------------------------------------------------------------------- energy
def test_ledger_phases_attribution_reconcile():
    from repro.core.elastic import ElasticScheduler
    sched = ElasticScheduler(1)
    led = obs_energy.EnergyLedger(sched)
    led.charge("busy", 2.0)
    led.charge("awake_idle", 1.0)
    led.charge("standby", 10.0)
    led.charge("busy", -1.0)                    # ignored, not negative
    rep = led.report
    assert rep.active_joules == pytest.approx(3.0 * sched.p_active)
    assert rep.standby_joules == pytest.approx(10.0 * sched.p_standby)
    assert rep.busy_core_seconds == pytest.approx(2.0)
    pjs = led.attribute([101, 102, 103, 104])
    assert len(pjs) == 4 and len(set(pjs)) == 1     # even split
    assert sum(pjs) == pytest.approx(rep.total_joules * 1e12)
    rec = led.reconcile()
    assert rec["ok"]
    assert rec["attributed_plus_unattributed"] == pytest.approx(
        rec["total_joules"])
    led.charge("busy", 0.5)                     # new unattributed energy
    assert led.reconcile()["ok"]
    led.attribute_bits(1 << 20)
    snap = led.snapshot()
    assert snap["indexed_bits"] == 1 << 20
    assert snap["pj_per_indexed_bit"] > 0
    op = snap["operating_points"]
    assert op["standby_mode"] in ("rbb", "cg")
    assert op["standby_rbb_w"] < op["standby_cg_w"] < op["active_w"]


# -------------------------------------------------------------- integration
def test_traced_storm_reconstructs_every_span_chain(installed_tracer):
    tracer = installed_tracer
    db = _mk_db()
    nq = 1000
    queries = _mixed_queries(np.random.default_rng(1), 16, nq)
    svc = db.serve(max_batch=128, max_delay_ms=1.0, idle_after_ms=500.0)
    futs = _storm(svc, queries, callers=8)
    m = svc.metrics()
    ledger = svc.ledger
    svc.close()

    spans = tracer.spans()
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, {})[s.name] = s
    waves = {s.attrs["wave"]: s for s in spans if s.name == "coalesce"}
    assert waves                                # at least one wave ran
    for f in futs:
        assert f.trace_id is not None
        chain = by_trace[f.trace_id]
        # the full per-query chain, correctly parented
        assert {"admission", "queue", "serve"} <= set(chain)
        assert chain["admission"].parent_id == 0
        assert chain["queue"].parent_id == chain["admission"].span_id
        assert chain["serve"].parent_id == chain["queue"].span_id
        # ...and joined to its wave's coalesce subtree via the wave id
        wid = chain["serve"].attrs["wave"]
        assert chain["queue"].attrs["wave"] == wid
        assert wid in waves
        assert chain["serve"].attrs["mode"] in ("preferred", "fallback")
        assert chain["serve"].attrs["pj"] >= 0.0
    # the wave subtree nests device.execute/dispatch/reassembly under
    # coalesce in the wave's own trace
    for name in ("device.execute", "bucket.dispatch", "reassembly"):
        assert any(s.name == name and s.trace_id in
                   {w.trace_id for w in waves.values()} for s in spans)
    # per-query pJ + the not-yet-attributed remainder == scheduler total
    per_q = ledger.per_query_pj()
    assert len(per_q) == nq
    attributed_j = sum(pj for _, pj in per_q) * 1e-12
    rec = ledger.reconcile()
    assert rec["ok"], rec
    total = svc.energy.total_joules
    assert np.isclose(attributed_j + ledger.snapshot()
                      ["unattributed_joules"], total, rtol=1e-6)
    assert m.energy is not None
    assert m.energy["pj_per_query_mean"] > 0


def test_concurrent_telemetry_readers_never_tear(installed_tracer):
    db = _mk_db()
    nq = 1000
    queries = _mixed_queries(np.random.default_rng(2), 16, nq)
    svc = db.serve(max_batch=64, max_delay_ms=0.5, idle_after_ms=500.0)
    stop = threading.Event()
    errs = []

    def reader():
        try:
            while not stop.is_set():
                m = svc.metrics()
                assert m.served >= 0
                h = svc.health()
                assert "wave_retries" in h
                db.cache_stats()
                obs_export.prometheus_text(svc.registry)
        except BaseException as e:              # noqa: BLE001 — reported
            errs.append(e)

    readers = [threading.Thread(target=reader) for _ in range(3)]
    for th in readers:
        th.start()
    try:
        futs = _storm(svc, queries, callers=8)
    finally:
        stop.set()
        for th in readers:
            th.join()
    assert not errs
    resolved = sum(1 for f in futs if f.done() and f.exception() is None)
    assert resolved == nq
    # the counters reconcile with the futures that actually resolved
    assert svc.metrics().served == nq
    svc.close()


def test_fault_event_lands_inside_interrupted_span(installed_tracer):
    from repro.fault import FaultInjector, FaultPlan, FaultSpec
    tracer = installed_tracer
    db = _mk_db()
    queries = _mixed_queries(np.random.default_rng(3), 16, 64)
    svc = db.serve(max_batch=32, max_delay_ms=0.5, idle_after_ms=500.0,
                   retry_base_ms=0.5)
    plan = FaultPlan((FaultSpec("engine.dispatch", "dispatch_error",
                                occurrence=1),))
    with FaultInjector(plan) as inj:
        futs = _storm(svc, queries, callers=4)
    svc.close()
    assert inj.fired("engine.dispatch")
    assert all(f.exception() is None for f in futs)     # retried through
    events = [s for s in tracer.spans()
              if s.name == "fault.dispatch_error"]
    assert events
    by_id = {s.span_id: s for s in tracer.spans()}
    for ev in events:
        assert ev.duration_s == 0.0
        # parented to the live span it interrupted (the wave's dispatch
        # machinery on the scheduler thread), in that span's trace
        assert ev.parent_id != 0
        parent = by_id.get(ev.parent_id)
        if parent is not None:                  # parent may still be live
            assert parent.trace_id == ev.trace_id
    # the injector's own event log carries the trace/span join too
    ev = inj.events[0]
    assert ev.get("trace") and ev.get("span")


def test_maintenance_task_chains_to_submitter_context(installed_tracer,
                                                      tmp_path):
    tracer = installed_tracer
    db = BitmapDB(_schema(), path=str(tmp_path / "d"), spill_records=128,
                  backend="ref")
    rng = np.random.default_rng(4)
    svc = db.serve(max_delay_ms=0.5, idle_after_ms=500.0)
    half = 8
    for _ in range(4):
        enc = np.stack([rng.integers(0, half, 256, dtype=np.int32),
                        rng.integers(half, 16, 256, dtype=np.int32)],
                       axis=1)
        with tracer.span("ingest"):
            db.append_encoded(enc)
    assert svc._maint_ex.flush(30)
    svc.close()
    spans = tracer.spans()
    maint = [s for s in spans if s.name.startswith("maintenance.")]
    assert maint                                # spills ran in background
    by_id = {s.span_id: s for s in spans}
    ingest = {s.span_id for s in spans if s.name == "ingest"}
    # the background task's span is parented to the span that scheduled
    # it (captured at submit time, crossed the worker thread): the
    # append's splice, which chains up to the caller's ingest span
    chained = [s for s in maint if s.parent_id in by_id
               and by_id[s.parent_id].name == "ingest.splice"]
    assert chained
    for s in chained:
        append = by_id[by_id[s.parent_id].parent_id]
        assert append.name == "ingest.append"
        assert append.parent_id in ingest
    assert any(s.name.startswith("store.") or s.name.startswith("spill")
               for s in spans)


INGEST_CHILDREN = ("ingest.upload", "ingest.create", "ingest.wait",
                   "ingest.splice", "ingest.count")


@pytest.mark.parametrize("rows,traced", [(512, True), (0, True),
                                         (512, False)],
                         ids=["one-block", "empty-block", "tracer-off"])
def test_append_encoded_span_tree(rows, traced):
    """One ``append_encoded`` with records is one ``ingest.append`` whose
    five children follow one another inside it, in order; an empty block
    records nothing; no append reads its counts back, whether or not a
    tracer is installed."""
    reads = itertools.count()
    tracer = obs_trace.Tracer(clock=lambda: float(next(reads)))
    if traced:
        obs_trace.install(tracer)
    counter = obs_metrics.GLOBAL.counter("db_ingest_readbacks_total")
    before = counter.value
    db = BitmapDB(_schema(), backend="ref")
    rng = np.random.default_rng(7)
    enc = np.stack([rng.integers(0, 8, rows, dtype=np.int32),
                    rng.integers(8, 16, rows, dtype=np.int32)], axis=1)
    try:
        assert db.append_encoded(enc) == rows
    finally:
        obs_trace.uninstall(tracer)
    assert counter.value - before == 0
    spans = tracer.spans()
    if not (rows and traced):
        assert spans == []
        return
    (root,) = [s for s in spans if s.name == "ingest.append"]
    assert root.parent_id == 0
    assert root.attrs == {"records": rows, "backend": "ref"}
    kids = sorted((s for s in spans if s.parent_id == root.span_id),
                  key=lambda s: s.t0)
    assert tuple(s.name for s in kids) == INGEST_CHILDREN
    assert len(spans) == 1 + len(kids)
    assert all(s.trace_id == root.trace_id for s in kids)
    assert root.t0 < kids[0].t0 and kids[-1].t1 < root.t1
    for a, b in zip(kids, kids[1:]):
        assert a.t0 < a.t1 < b.t0


def test_disabled_path_records_nothing():
    assert obs_trace.TRACER is None
    db = _mk_db(n=512)
    queries = _mixed_queries(np.random.default_rng(5), 16, 32)
    svc = db.serve(max_delay_ms=0.5, idle_after_ms=500.0)
    futs = _storm(svc, queries, callers=2)
    assert all(f.trace_id is None for f in futs)
    m = svc.metrics()
    assert m.served == 32
    assert m.energy["total_joules"] > 0         # ledger runs regardless
    assert svc.ledger.reconcile()["ok"]
    svc.close()


def test_service_registry_grafts_lower_layers():
    db = _mk_db(n=512)
    svc = db.serve(max_delay_ms=0.5)
    _storm(svc, _mixed_queries(np.random.default_rng(6), 16, 16),
           callers=2)
    names = dict(svc.registry.collect())
    assert "served_total" in names
    assert "db_plan_cache_misses_total" in names
    assert any(n.startswith("engine_") for n in names)
    assert names["served_total"].value == 16
    # engine counters moved: waves/queries/dispatches all advanced
    assert names["engine_engine_queries_total"].value >= 16
    svc.close()
