"""The ingest spans on the device trace's clock, from a trace recorded on
a TPU v5e (``tests/data/ingest.xplane.pb``).

Recorded with the tracer installed while the profiler ran, four appends
of 2^18 records of 32 8-bit words into a 256-key session, after a warm-up
session of the same widths::

    python tests/record_ingest_trace.py --out tests/data/ingest.xplane.pb

which writes the ``.xplane.pb`` and ``ingest.xplane.pb.json`` (the
``perf_counter`` reading taken inside the ``bench.sync`` annotation, and
the spans).  ``bench/trace_reduce.py`` puts the device's events on the
spans' clock; these tests check that the two clocks agree and that the
spans name every device-idle gap of an append.
"""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace_reduce  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402

TRACE = os.path.join(ROOT, "tests", "data", "ingest.xplane.pb")
#: how far a device event may lie outside the span that caused it
TOL_S = 0.5e-3
CHILDREN = ("ingest.upload", "ingest.create", "ingest.splice",
            "ingest.wait", "ingest.readback")


@pytest.fixture(scope="module")
def recorded():
    with open(TRACE + ".json") as f:
        meta = json.load(f)
    spans = []
    for d in meta["spans"]:
        sp = obs_trace.Span(d["name"], d["trace"], d["span"], d["parent"],
                            d["t0"], d["attrs"])
        sp.t1 = d["t1"]
        spans.append(sp)
    red = trace_reduce.reduce(TRACE, meta["sync_pc"])
    return red, spans, meta


def _appends(spans):
    """``[(append, {child name: child})]`` in time order."""
    kids: dict = {}
    for s in spans:
        kids.setdefault(s.parent_id, {})[s.name] = s
    roots = sorted((s for s in spans if s.name == "ingest.append"),
                   key=lambda s: s.t0)
    return [(a, kids[a.span_id]) for a in roots]


def test_cam_match_runs_between_its_create_and_its_wait(recorded):
    red, spans, meta = recorded
    appends = _appends(spans)
    assert len(appends) == meta["blocks"]
    runs = sorted((s, e) for name, s, e in red.modules
                  if re.match(r"^jit_cam_match\b", name))
    assert len(runs) == len(appends)
    for (s, e), (_, kids) in zip(runs, appends):
        assert s >= kids["ingest.create"].t0 - TOL_S
        assert e <= kids["ingest.wait"].t1 + TOL_S


def test_no_idle_gap_inside_an_append_is_named_by_the_append(recorded):
    """Each device-idle gap whose middle lies inside an ``ingest.append``
    is named by one of its children, never by the append itself."""
    red, spans, _ = recorded
    roots = [a for a, _ in _appends(spans)]
    gaps = red.gaps()
    labels = trace_reduce.label_gaps(red, spans, top=len(gaps))
    inside = [label for (s, e), (label, _) in zip(gaps, labels)
              if any(a.t0 <= (s + e) / 2 < a.t1 for a in roots)]
    assert inside
    assert set(inside) <= set(CHILDREN)
