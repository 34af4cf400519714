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

#: the open-loop SSB cell's entries, which BENCHMARK.json leaves out until
#: its bounds are measured on the chip (PERF.md, Open questions)
SSB_CELL = pathlib.Path(ROOT) / "bench" / "tests" / "ssb-sf1.q12-q13.json"

TINY = {
    "ssb-sf1.q12-q13": (
        dict(lineorder_rows=5000, customer_rows=300, supplier_rows=40,
             part_rows=2000, block_records=2048),
        dict(rate_per_s=200, warm_max_q=4, warm_burst_s=0.2,
             sample_per_template=1)),
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
    """``bm`` with the open-loop SSB cell's entries added."""
    extra = json.loads(SSB_CELL.read_text())
    for key, entries in extra.items():
        have = {e["name"] for e in bm[key]}
        bm[key] += [e for e in entries if e["name"] not in have]
    return bm


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of the benchmark whose BENCHMARK.json also holds the
    open-loop SSB cell."""
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
