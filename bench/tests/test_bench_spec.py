"""BENCHMARK.json against the files it names, and the trace reduction on
a small trace recorded on a TPU v5e (``bench/testdata``)."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, peaks, trace_reduce  # noqa: E402
from bench.tests.test_bench_run import with_ssb_cell  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TRACE = os.path.join(ROOT, "bench", "testdata", "create_index.xplane.pb")

BM = harness.load_benchmark()
#: BENCHMARK.json and the SSB cells it leaves out until the chip can
#: measure them (``bench/tests/ssb-sf1.q12-q13.json``, ``.streams.json``)
ALL = with_ssb_cell(harness.load_benchmark())


def test_names_units_and_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in ALL[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in ALL["end_to_end"] + ALL["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert 1 <= BM["run_seconds"] <= 51
    assert {"setup_s"} <= {m["name"] for m in BM["end_to_end"]}


@pytest.mark.parametrize("cfg", ALL["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    c = harness.load_config(cfg["name"])
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    assert set(cfg["reduced"]) == set(c.sizes["reduced"])
    assert any(w["config"] == cfg["name"] for w in ALL["workloads"])


@pytest.mark.parametrize("cell", ALL["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    name = cell["name"]
    assert name == f"{cell['config']}.{cell['traffic']}"
    assert harness.load_mix(cell["traffic"])["generator"]
    e2e = {m["name"] for m in harness.cell_metrics(ALL, name, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.cell_metrics(ALL, name, "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("m", ALL["per_layer"], ids=lambda m: m["name"])
def test_metric_readers_declare_what_the_json_says(m):
    mod = harness.load_metric(m["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                               m["moves"])
    assert callable(mod.read)


def test_peaks_refuse_an_unknown_chip():
    assert peaks.peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


@pytest.fixture(scope="module")
def reduced():
    with open(TRACE + ".json") as f:
        meta = json.load(f)
    return trace_reduce.reduce(TRACE, meta["sync_pc"]), meta


def test_trace_reduces_to_busy_and_idle(reduced):
    red, meta = reduced
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    assert red.busy_s == pytest.approx(meta["busy_s"])
    gaps = red.gaps()
    assert gaps and all(e > s for s, e in gaps)
    assert sum(e - s for s, e in gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert red.t0 <= meta["sync_pc"] <= red.t1


def test_trace_names_the_index_creation_kernels(reduced):
    red, _ = reduced
    names = {m[0] for m in red.modules}
    assert any(re.match(r"^jit_cam_match\b", n) for n in names), names
    assert any(re.match(r"^jit_bit_transpose\b", n) for n in names), names
    assert red.module_time(r"^jit_cam_match\b") > 0
    top = trace_reduce.top_ops(red, 3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1] > 0


def test_idle_gaps_are_labelled_by_the_open_span(reduced):
    red, _ = reduced

    class S:
        def __init__(self, name, t0, t1):
            self.name, self.t0, self.t1 = name, t0, t1

    spans = [S("outer", red.t0, red.t1), S("inner", red.t0, red.t0)]
    gaps = trace_reduce.label_gaps(red, spans, top=4)
    assert 1 <= len(gaps) <= 4
    assert all(label == "outer" and secs > 0 for label, secs in gaps)
    assert trace_reduce.label_gaps(red, [], top=1)[0][0] == "no span"
