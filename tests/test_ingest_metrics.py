"""The session layer's benchmark metrics (``bench/metrics/upload_ms.py``,
``readback_ms.py``, ``host_gap_ms.py``, ``readbacks_per_block.py``,
``narrow_uploads_per_block.py``) on the
CPU: a tiny traced ``bic-paper.load`` run, a program without the ingest
spans and counter, and the exact idle-time intersection of
``host_gap_ms``."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace_reduce  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402

#: the load cell cut to CPU size: four 2048-record blocks per session
TINY_LOAD = (dict(block_records=2048, session_records=8192,
                  pool_records=8192),
             dict(sample_blocks=2))


def test_ingest_metrics_read_a_traced_load():
    """A tiny traced load run (no profiler: the CPU has no device trace)
    gives the session's span and counter metrics; the pipelined append
    makes no readback, and every block of 8-bit words crosses as uint8."""
    sizes, mix = TINY_LOAD
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        _, gen = harness.make_generator("bic-paper.load", 6, tracer,
                                        sizes=sizes, mix_overrides=mix)
        gen.run.window_started = lambda t0: None
        gen.setup()
        c0 = harness._counters()
        gen.measure(0.3)
        c1 = harness._counters()
    finally:
        obs_trace.uninstall(tracer)
    ctx = harness.LayerContext(
        gen=gen, window=(gen.t0, gen.t1), spans=tracer.spans(),
        counters={k: c1[k] - c0.get(k, 0) for k in c1}, compiles=0,
        trace=None, peaks=None)
    got = {m: harness.load_metric(m).read(ctx)
           for m in ("upload_ms", "readback_ms", "readbacks_per_block",
                     "host_gap_ms", "append_ms", "narrow_uploads_per_block")}
    gen.release()
    # the append reads no counts back: no readback span, no readback
    assert got["upload_ms"] > 0 and got["readback_ms"] is None
    assert got["upload_ms"] < got["append_ms"]
    assert got["readbacks_per_block"] == 0.0
    assert got["narrow_uploads_per_block"] == 1.0
    assert got["host_gap_ms"] is None


@pytest.mark.parametrize("name", ["upload_ms", "readback_ms", "host_gap_ms",
                                  "readbacks_per_block",
                                  "narrow_uploads_per_block"])
def test_ingest_metrics_read_nothing_without_their_spans(name):
    """A program without the ingest spans and counter (or a run without a
    trace) gives no reading, and no error."""
    ctx = harness.LayerContext(
        gen=type("G", (), {"blocks_done": 3}), window=(0.0, 1.0),
        spans=[], counters={}, compiles=0, trace=None, peaks=None)
    assert harness.load_metric(name).read(ctx) is None


@pytest.mark.parametrize("name, counter", [
    ("readbacks_per_block", "db_ingest_readbacks_total"),
    ("narrow_uploads_per_block", "db_ingest_narrow_uploads_total")])
def test_counter_metrics_are_the_counter_over_the_blocks(name, counter):
    """The counter's change in the window over the blocks appended in it;
    no reading without blocks."""
    gen = type("G", (), {"blocks_done": 8})
    ctx = harness.LayerContext(
        gen=gen, window=(0.0, 1.0), spans=[], counters={counter: 6},
        compiles=0, trace=None, peaks=None)
    metric = harness.load_metric(name)
    assert metric.read(ctx) == 0.75
    gen.blocks_done = 0
    assert metric.read(ctx) is None


def _span(name, t0, t1):
    sp = obs_trace.Span(name, 1, 1, 0, t0, {})
    sp.t1 = t1
    return sp


def test_host_gap_ms_is_idle_time_inside_whole_appends():
    # busy [1, 2] and [4, 7] of the window [0, 10]: idle [0, 1], [2, 4],
    # [7, 10]
    red = trace_reduce.Reduced(t0=0.0, t1=10.0, devices=1, busy_s=4.0,
                               busy=[(1.0, 2.0), (4.0, 7.0)], ops={},
                               modules=[])
    spans = [_span("ingest.append", 0.5, 3.0),     # idle 0.5 + 1.0
             _span("ingest.append", 6.0, 9.0),     # idle 2.0
             _span("ingest.append", 9.5, 11.0),    # ends past the window
             _span("ingest.append", -1.0, 0.5),    # starts before it
             _span("bench.append", 0.0, 10.0)]
    ctx = harness.LayerContext(gen=None, window=(0.0, 10.0), spans=spans,
                               counters={}, compiles=0, trace=red,
                               peaks=None)
    metric = harness.load_metric("host_gap_ms")
    assert metric.read(ctx) == pytest.approx(1e3 * (1.5 + 2.0) / 2)
    ctx.trace = None
    assert metric.read(ctx) is None
