"""The harness on the CPU at tiny sizes: the refusal without a chip, a
whole run past the chip check with the timed path sound and broken, the
controls, and discovery of a new configuration, mix and metric by file
name alone."""
import contextlib
import dataclasses
import io
import json
import os
import pathlib
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import control, harness, run  # noqa: E402

#: the entries of the SSB cells that BENCHMARK.json leaves out until the
#: chip can measure them (PERF.md, Open questions): the open loop on Q1.2
#: and Q1.3, and the closed loop on all 13 templates
WAITING = [pathlib.Path(ROOT) / "bench" / "tests" / f"{cell}.json"
           for cell in ("ssb-sf1.q12-q13", "ssb-sf1.streams")]

TINY = {
    "ssb-sf1.q12-q13": (
        dict(lineorder_rows=5000, customer_rows=300, supplier_rows=40,
             part_rows=2000, block_records=2048),
        dict(rate_per_s=200, warm_max_q=4, warm_burst_s=0.2,
             sample_per_template=1)),
    "ssb-sf1.streams": (
        dict(lineorder_rows=5000, customer_rows=300, supplier_rows=40,
             part_rows=2000, block_records=2048),
        dict(streams=8, warm_max_q=8, warm_s=0.2, sample_per_template=1)),
    "bic-paper.load": (
        dict(block_records=2048, session_records=8192, pool_records=8192),
        dict(sample_blocks=2)),
}


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """A run points JAX's persistent cache at the checkout; the tests
    keep the process's JAX settings as they found them."""
    from repro import jaxcache
    monkeypatch.setattr(jaxcache, "enable", lambda: None)


def with_ssb_cell(bm: dict) -> dict:
    """``bm`` with the waiting SSB cells' entries added; an entry that
    ``bm`` already has gains the waiting cells in its ``workloads``."""
    for path in WAITING:
        for key, entries in json.loads(path.read_text()).items():
            have = {e["name"]: e for e in bm[key]}
            for e in entries:
                if e["name"] not in have:
                    bm[key].append(e)
                elif "workloads" in e:
                    have[e["name"]]["workloads"] += e["workloads"]
    return bm


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of the benchmark whose BENCHMARK.json also holds the
    waiting SSB cells."""
    root = tmp_path_factory.mktemp("checkout")
    (root / "bench").symlink_to(pathlib.Path(ROOT) / "bench")
    (root / "BENCHMARK.json").write_text(
        json.dumps(with_ssb_cell(harness.load_benchmark())))
    return root


def _run(cell, root, seed=3, seconds=0.5, **kw):
    sizes, mix = TINY[cell]
    out = harness.run_cell(cell, seed, seconds, False,
                           t_process=time.perf_counter(),
                           root=pathlib.Path(root), sizes=sizes,
                           mix_overrides=mix, **kw)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        harness.print_result(out)
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


def test_run_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "bic-paper.load", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "needs 1 TPU" in out.err


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell, checkout):
    line = _run(cell, checkout)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {
        m["name"] for m in harness.cell_metrics(
            harness.load_benchmark(checkout), cell, "end_to_end")}
    assert line["device"]["platform"] == "cpu"
    assert all(v["value"] == 0 for v in line["checks"].values())


def _flip_served_answer(monkeypatch):
    """An answer altered where it is produced: the first row of every
    wave loses its first bit and its count grows by one."""
    from repro.engine import batch

    orig = batch._serve

    def serve(*a, **kw):
        rows, counts = orig(*a, **kw)
        return rows.at[0, 0].set(rows[0, 0] ^ 1), counts.at[0].add(1)

    monkeypatch.setattr(batch, "_serve", serve)


def _flip_index_bit(monkeypatch):
    """An index row altered where it is produced: index creation sets one
    bit that no record holds."""
    from repro.engine import backends

    for name in ("ref", "pallas", "bulk"):
        be = backends._REGISTRY[name]
        orig = be.create_index

        def create(records, keys, _orig=orig):
            out = _orig(records, keys)
            return out.at[0, 0].set(out[0, 0] ^ 1)

        monkeypatch.setitem(backends._REGISTRY, name,
                            dataclasses.replace(be, create_index=create))


@pytest.mark.parametrize("cell,fault", [
    ("ssb-sf1.q12-q13", _flip_served_answer),
    ("ssb-sf1.streams", _flip_served_answer),
    ("bic-paper.load", _flip_index_bit)])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch,
                                          checkout):
    fault(monkeypatch)
    line = _run(cell, checkout, seed=4)
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["checks"].values())


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(cell, checkout):
    sizes, mix = TINY[cell]
    for seed in (1, 2, 3):
        out = control.control(cell, seed, seconds=2.0, sessions=2,
                              sizes=sizes, mix_overrides=mix, root=checkout)
        assert out["correct"] is False, out


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A copy of the benchmark gains a configuration, a mix and a metric
    as new files plus entries in BENCHMARK.json; the harness runs the new
    cell and reports the new metric with no other file edited."""
    shutil.copytree(pathlib.Path(ROOT) / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    bm = harness.load_benchmark()
    b = tmp_path / "bench"
    sizes = json.loads((b / "configs" / "bic-paper.json").read_text())
    sizes.update(name="tiny-bic", num_keys=64, words_per_record=4,
                 word_bits=6)
    (b / "configs" / "tiny-bic.json").write_text(json.dumps(sizes))
    shutil.copyfile(b / "configs" / "bic-paper.py",
                    b / "configs" / "tiny-bic.py")
    (b / "mixes" / "trickle.json").write_text(json.dumps(
        {"generator": "load", "sample_blocks": 1, "profile_lead_s": 0,
         "profile_s": 1}))
    (b / "metrics" / "blocks_seen.py").write_text(
        'LAYER = "load generator (bench/traffic.py)"\nUNIT = "blocks"\n'
        'MOVES = "ingest_rec_s"\n\n\ndef read(ctx):\n'
        '    return float(ctx.gen.blocks_done)\n')
    bm["configs"].append({"name": "tiny-bic", "source": "test",
                          "file": "bench/configs/tiny-bic.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-bic.trickle", "config": "tiny-bic",
                            "traffic": "trickle", "chips": 1, "why": "test"})
    next(m for m in bm["end_to_end"] if m["name"] == "ingest_rec_s"
         )["workloads"].append("tiny-bic.trickle")
    bm["per_layer"].append({"name": "blocks_seen", "unit": "blocks",
                            "better": "higher", "source": "host_clock",
                            "layer": "load generator (bench/traffic.py)",
                            "moves": "ingest_rec_s",
                            "workloads": ["tiny-bic.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cfg = harness.load_config("tiny-bic", b)
    assert cfg.sizes["num_keys"] == 64
    metric = harness.load_metric("blocks_seen", b)
    assert metric.MOVES == "ingest_rec_s"
    out = harness.run_cell(
        "tiny-bic.trickle", 9, 0.3, False, t_process=time.perf_counter(),
        root=tmp_path, sizes=TINY["bic-paper.load"][0])
    assert out["correct"] is True, out
    assert set(out["metrics"]) == {"ingest_rec_s", "setup_s"}
    ctx = harness.LayerContext(
        gen=type("G", (), {"blocks_done": 3}),
        window=(0, 1), spans=[], counters={}, compiles=0, trace=None,
        peaks=None)
    names = [m["name"] for m in harness.cell_metrics(
        harness.load_benchmark(tmp_path), "tiny-bic.trickle", "per_layer")]
    assert names == ["blocks_seen"]
    assert harness.load_metric(names[0], b).read(ctx) == 3.0


def test_append_ms_is_the_median_append_of_the_window(checkout):
    sizes, mix = TINY["bic-paper.load"]
    _, gen = harness.make_generator("bic-paper.load", 5, root=checkout,
                                    sizes=sizes, mix_overrides=mix)
    gen.setup()
    gen.measure(0.3)
    ctx = harness.LayerContext(gen=gen, window=(gen.t0, gen.t1), spans=[],
                               counters={}, compiles=0, trace=None,
                               peaks=None)
    metric = harness.load_metric("append_ms")
    assert len(gen.append_ms) == gen.blocks_done > 0
    assert metric.read(ctx) == float(np.median(gen.append_ms)) > 0
    gen.release()
    ctx.gen = object()              # a generator that times no appends
    assert metric.read(ctx) is None


def test_host_watch_counts_full_collections():
    import gc

    with harness.HostWatch() as w:
        gc.collect()
        time.sleep(3 * w.TICK_S)
    n = w.notes()
    assert n["gc_full"] >= 1 and n["gc_full_max_ms"] > 0
    assert n["gc_ms"] >= n["gc_full_max_ms"]
    assert n["late_max_ms"] >= 0 and n["pauses"] >= n["stalls"] >= 0
    assert w._on_gc not in gc.callbacks


def test_seeded_schedule_keeps_its_work():
    """Two seeds give the same gaps and templates, in other orders."""
    from bench import traffic

    cfg = harness.load_config("ssb-sf1")
    mix = harness.load_mix("q12-q13")
    a, b = (traffic.OpenLoop(cfg, dict(mix, rate_per_s=400), s,
                             harness.Run(None, 0, 0)) for s in (1, 2))
    for d in (a, b):
        d.templates = list(cfg.module.TEMPLATES)
    sa, sb = a.schedule("window", 2.0), b.schedule("window", 2.0)
    assert len(sa.due) == len(sb.due) == 800
    assert np.array_equal(np.sort(sa.gaps), np.sort(sb.gaps))
    assert not np.array_equal(sa.gaps, sb.gaps)
    assert np.allclose(np.diff(sa.due), sa.gaps[1:])
    assert sorted(sa.templates) == sorted(sb.templates)
    assert sa.templates != sb.templates
    again = a.schedule("window", 2.0)
    assert np.array_equal(again.due, sa.due) and again.queries == sa.queries


@pytest.mark.parametrize("cell", ["ssb-sf1.streams"])
def test_traced_streams_run_reads_its_span_metrics(cell, checkout,
                                                   monkeypatch):
    """``--trace 1`` on a closed-loop cell: the span and counter metrics
    come from the run (the CPU has no device trace, so the profiler is
    left out and the trace's metrics read nothing)."""
    monkeypatch.setattr(harness.Run, "window_started", lambda self, t0: None)
    sizes, mix = TINY[cell]
    out = harness.run_cell(cell, 6, 0.5, True,
                           t_process=time.perf_counter(), root=checkout,
                           sizes=sizes, mix_overrides=mix)
    assert out["correct"] is True, out
    listed = harness.cell_metrics(harness.load_benchmark(checkout), cell,
                                  "per_layer")
    assert set(out["metrics"]) == {m["name"] for m in listed
                                   if m["source"] != "device_trace"}
    assert out["metrics"]["wave_size"]["value"] >= 1




class _FakeService:
    """Serves submitted queries in first-in first-out waves of up to
    ``wave`` from a thread of its own.  With ``log`` it logs every submit
    (with the query's expression) and every read of a count, in order;
    without, it keeps nothing of a query once it is served."""

    def __init__(self, wave: int, log: bool = True):
        import collections
        import threading

        self.wave = wave
        self.log: list | None = [] if log else None
        self._queue = collections.deque()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def submit(self, expr):
        fut = _FakeFuture(self)
        with self._lock:
            if self.log is not None:
                fut.expr = repr(expr)
                self.log.append(("submit", fut))
            self._queue.append(fut)
        return fut

    def _serve(self):
        while not self._stop.wait(0.001):
            with self._lock:
                take = [self._queue.popleft()
                        for _ in range(min(self.wave, len(self._queue)))]
            for fut in take:
                fut.ev.set()

    def close(self):
        self._stop.set()
        self._thread.join(timeout=10)
        assert not self._thread.is_alive()


class _FakeFuture:
    trace_id = None

    def __init__(self, svc):
        import threading

        self.svc, self.ev = svc, threading.Event()

    def wait(self, timeout=None):
        return self.ev.wait(timeout)

    def done(self):
        return self.ev.is_set()

    def exception(self, timeout=None):
        return None

    @property
    def count(self):
        assert self.done()
        if self.svc.log is not None:
            with self.svc._lock:
                self.svc.log.append(("read", self))
        return 7

    @property
    def rows(self):
        return np.zeros(2, np.uint32)


def _generator(checkout, seed, **mix):
    from bench import traffic

    sizes, tiny = TINY["ssb-sf1.streams"]
    _, gen = harness.make_generator("ssb-sf1.streams", seed, root=checkout,
                                    sizes=sizes,
                                    mix_overrides=dict(tiny, streams=5,
                                                       **mix))
    assert isinstance(gen, traffic.ClosedLoop)
    gen.templates = list(gen.mix["templates"])
    # a run's set-up has built expressions before its window: the first
    # takes seconds of imports
    traffic.to_expr(next(gen.stream(gen.WARM, 0))[1])
    return gen


def _drive(checkout, seed, wave, seconds=0.3, log=True):
    """A closed-loop window against :class:`_FakeService`: the generator,
    the window's record in submission order (each query drawn again from
    its stream's seed, the sampled rows by position), and the service's
    log."""
    gen = _generator(checkout, seed)
    gen.svc = svc = _FakeService(wave, log)
    try:
        w = gen.drive_streams(gen.WINDOW, seconds,
                              keep=gen.mix["sample_per_template"])
    finally:
        svc.close()
    f = gen.flatten(w["logs"])
    j_of = {(int(s), int(k)): j
            for j, (s, k) in enumerate(zip(f["stream"], f["k"]))}
    f["template"], f["query"], f["priority"] = gen.replay(
        gen.WINDOW, f["stream"], f["k"])
    f["rows"] = {j_of[key]: r for key, r in w["rows"].items()}
    return gen, f, svc.log


def test_closed_loop_keeps_one_query_per_stream(checkout):
    """Each stream has at most one query outstanding and submits its next
    only after it has read the last one's count; each runs the templates
    in its own seeded order; a seed gives every stream the same queries
    whatever the service's pace."""
    gen, w, log = _drive(checkout, 11, wave=3)
    n = gen.mix["streams"]
    subs = [f for kind, f in log if kind == "submit"]
    assert len(subs) == len(w["query"]) > 3 * n
    when = {(kind, id(f)): i for i, (kind, f) in enumerate(log)}
    assert not w["failed"].any() and (w["count"] == 7).all()
    per_stream: dict = {}
    for j, s in enumerate(w["stream"]):
        per_stream.setdefault(int(s), []).append(j)
    assert sorted(per_stream) == list(range(n))
    for s, js in per_stream.items():
        # submit(j) < read(j) < submit(next j) for the stream's queries
        for a, b in zip(js, js[1:]):
            assert (when["submit", id(subs[a])] < when["read", id(subs[a])]
                    < when["submit", id(subs[b])])
        assert [w["k"][j] for j in js] == list(range(len(js)))
        _, order = gen.order(gen.WINDOW, s)
        assert sorted(order) == sorted(gen.templates)
        assert [w["template"][j] for j in js] == [
            order[k % len(order)] for k in range(len(js))]
    orders = {tuple(gen.order(gen.WINDOW, s)[1]) for s in range(n)}
    assert len(orders) > 1                  # each stream its own order

    # the sampled rows: each template's lowest priorities among the
    # queries read, as many as the mix asks for
    k = gen.mix["sample_per_template"]
    for t in set(w["template"]):
        js = sorted((w["priority"][j], j) for j, tt in enumerate(w["template"])
                    if tt == t)
        assert {j for j in w["rows"] if w["template"][j] == t} == {
            j for _, j in js[:k]}

    def queries(w):
        out: dict = {}
        for s, q in zip(w["stream"], w["query"]):
            out.setdefault(int(s), []).append(q)
        return out

    again = queries(_drive(checkout, 11, wave=1, seconds=0.2)[1])
    first = queries(w)
    for s, qs in again.items():
        m = min(len(qs), len(first[s]))
        assert m > 0 and qs[:m] == first[s][:m]
    other = queries(_drive(checkout, 12, wave=3, seconds=0.2)[1])
    assert any(other[s][:2] != first[s][:2] for s in other)


def test_closed_loop_replays_what_the_clients_sent(checkout):
    """The queries the comparison draws again from the streams' seeds are
    the ones the clients submitted, in the order the service saw them."""
    from bench import traffic

    _, w, log = _drive(checkout, 13, wave=4)
    sent = [f.expr for kind, f in log if kind == "submit"]
    assert len(sent) == len(w["query"]) > 0
    assert sent == [repr(traffic.to_expr(q)) for q in w["query"]]


def test_closed_loop_keeps_no_object_per_query(checkout):
    """The clients record a window in arrays: the objects the collector
    tracks after the window do not grow with the queries it served."""
    import gc

    gen = _generator(checkout, 14)
    gen.svc = svc = _FakeService(wave=5, log=False)
    try:
        gen.drive_streams(gen.WINDOW, 0.05, keep=0)     # threads, imports
        gc.collect()
        before = len(gc.get_objects())
        w = gen.drive_streams(gen.WINDOW, 0.5, keep=0)
        gc.collect()
        after = len(gc.get_objects())
    finally:
        svc.close()
    served = sum(g.n for g in w["logs"])
    assert served > 500
    assert after - before < 50 * gen.mix["streams"], (before, after, served)


def test_stream_log_grows_without_losing_records():
    from bench import traffic

    log = traffic.StreamLog(cap=2)
    for i in range(9):
        k = log.add(float(i))
        log.count[k] = 10 * i
        log.failed[k] = i % 3 == 0
    assert log.n == 9 and len(log.t_sub) == 16
    assert list(log.t_sub[:9]) == [float(i) for i in range(9)]
    assert list(log.count[:9]) == [10 * i for i in range(9)]
    assert list(log.failed[:9]) == [i % 3 == 0 for i in range(9)]
    assert np.isnan(log.t_read[:9]).all() and (log.count[9:] == -1).all()
    assert log.failed[9:].all()


def test_flatten_orders_the_streams_by_submit():
    from bench import traffic

    logs = [traffic.StreamLog(), traffic.StreamLog()]
    for s, t in [(0, 0.5), (1, 0.1), (1, 0.7), (0, 0.9), (1, 1.0)]:
        k = logs[s].add(t)
        logs[s].count[k] = 100 * s + k
    f = traffic.ClosedLoop.flatten(logs)
    assert list(f["t_sub"]) == [0.1, 0.5, 0.7, 0.9, 1.0]
    assert list(f["stream"]) == [1, 0, 1, 0, 1]
    assert list(f["k"]) == [0, 0, 1, 1, 2]
    assert list(f["count"]) == [100, 0, 101, 1, 102]


@pytest.mark.parametrize("per_stream", [2, 3])
def test_control_size_comes_from_the_cell(per_stream, checkout):
    """The control answers each stream's first ``control_per_stream``
    queries of the cell's mix, sampled as a run samples."""
    gen = _generator(checkout, 15, control_per_stream=per_stream)
    gen.make_data()
    gen.plan_control()
    assert len(gen.window.queries) == per_stream * gen.mix["streams"]
    first = [next(gen.stream(gen.WINDOW, s))[1]
             for s in range(gen.mix["streams"])]
    assert first == gen.window.queries[::per_stream]
    k = gen.mix["sample_per_template"]
    assert len(gen.sample) == k * len(set(gen.window.templates))
