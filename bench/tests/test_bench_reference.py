"""The plain references of the benchmark's configurations, on the CPU at
tiny sizes: the SSB generator's hierarchies, the NumPy evaluator against
the program, and the index reference of the paper's record format."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, reference, traffic  # noqa: E402

TINY_SSB = dict(lineorder_rows=6000, customer_rows=300, supplier_rows=40,
                part_rows=2000, block_records=2048)


@pytest.fixture(scope="module")
def ssb():
    cfg = harness.load_config("ssb-sf1")
    cfg.sizes.update(TINY_SSB)
    return cfg, cfg.module.generate(cfg.sizes, 7)


def test_ssb_columns_are_the_configs_key_rows():
    cfg = harness.load_config("ssb-sf1")
    cols = cfg.module.columns(cfg.sizes)
    assert [c for c, _ in cols] == list(cfg.sizes["columns"])
    assert sum(len(v) for _, v in cols) == cfg.sizes["key_rows"] == 1795
    assert all(v == sorted(v) for _, v in cols)


def test_ssb_generator_keeps_the_hierarchies(ssb):
    cfg, rows = ssb
    nr = cfg.module.NATION_REGION
    for side in ("c", "s"):
        assert np.array_equal(rows[f"{side}_city"] // 10,
                              rows[f"{side}_nation"])
        assert np.array_equal(nr[rows[f"{side}_nation"]],
                              rows[f"{side}_region"])
    assert np.array_equal(rows["p_brand1"] // 100, rows["p_category"])
    assert np.array_equal(rows["p_category"] // 10, rows["p_mfgr"])
    assert np.array_equal(rows["d_yearmonthnum"] // 100, rows["d_year"])
    assert rows["d_yearmonthnum"].max() <= 199808      # dbgen's last date
    assert rows["lo_quantity"].min() >= 1 and rows["lo_quantity"].max() <= 50


def test_ssb_generation_is_seeded(ssb):
    cfg, rows = ssb
    again = cfg.module.generate(cfg.sizes, 7)
    other = cfg.module.generate(cfg.sizes, 8)
    assert all(np.array_equal(rows[c], again[c]) for c in rows)
    assert not all(np.array_equal(rows[c], other[c]) for c in rows)


@pytest.mark.parametrize("template,clauses", [
    ("Q1.1", 72), ("Q1.2", 30), ("Q1.3", 30), ("Q2.1", 1), ("Q2.2", 8),
    ("Q2.3", 1), ("Q3.1", 6), ("Q3.2", 6), ("Q3.3", 24), ("Q3.4", 4),
    ("Q4.1", 2), ("Q4.2", 4), ("Q4.3", 2)])
def test_ssb_templates_keep_the_specs_dnf_width(template, clauses):
    from repro.db import Column, Schema
    from repro.db import expr as expr_mod
    from repro.engine import planner

    cfg = harness.load_config("ssb-sf1")
    schema = Schema([Column.categorical(n, v)
                     for n, v in cfg.module.columns(cfg.sizes)])
    q = cfg.module.draw(cfg.sizes, reference.rng(3, "queries"), template)
    pl = planner.plan(expr_mod.lower(traffic.to_expr(q), schema))
    assert len(pl.clauses) == clauses


def test_filter_reference_agrees_with_the_program(ssb):
    """Every template's counts and row sets from NumPy equal the program's
    answers over an index built by the program from the same rows."""
    import repro
    from repro.db import Column, Schema

    cfg, rows = ssb
    domains = cfg.module.columns(cfg.sizes)
    schema = Schema([Column.categorical(n, v) for n, v in domains])
    db = repro.BitmapDB(schema, capacity_words=512)
    db.append(rows)
    fr = reference.FilterReference(dict(domains), rows)
    r = reference.rng(4, "queries")
    qs = [cfg.module.draw(cfg.sizes, r, t)
          for t in cfg.module.TEMPLATES for _ in range(3)]
    res = db.query_many([traffic.to_expr(q) for q in qs])
    for q, got in zip(qs, res):
        want = fr.mask(q)
        assert got.count == fr.count(q) == np.count_nonzero(want)
        assert np.array_equal(got.ids, np.flatnonzero(want))
        assert np.array_equal(np.asarray(got.rows), fr.row(q))


def test_binned_control_is_a_superset(ssb):
    cfg, rows = ssb
    fr = reference.FilterReference(dict(cfg.module.columns(cfg.sizes)),
                                   rows)
    q = (("between", "lo_discount", 1, 3), ("lt", "lo_quantity", 25))
    exact, wide = fr.mask(q), fr.mask(q, cfg.module.CONTROL_BIN)
    assert np.all(wide[exact]) and wide.sum() > exact.sum()


def test_index_rows_reference_agrees_with_the_program():
    import repro

    cfg = harness.load_config("bic-paper")
    cfg.sizes.update(pool_records=4096)
    pool = cfg.module.generate_pool(cfg.sizes, 5)
    assert pool.shape == (4096, 32) and pool.dtype == np.int32
    db = repro.BitmapDB(num_keys=256, capacity_words=256)
    db.append_encoded(pool)
    got = np.asarray(db.indexer.view()[0])[:, :4096 // 32]
    assert np.array_equal(got, cfg.module.index_rows(cfg.sizes, pool))
    assert not np.array_equal(got, cfg.module.control_rows(cfg.sizes, pool))


def test_seed_streams_take_any_integer():
    for seed in (0, 2**31 + 7, -5, 2**70 + 3):
        a = reference.rng(seed, "data").integers(0, 1 << 30, 4)
        b = reference.rng(seed, "data").integers(0, 1 << 30, 4)
        assert np.array_equal(a, b)
    assert not np.array_equal(reference.rng(1, "data").integers(0, 99, 8),
                              reference.rng(1, "queries").integers(0, 99, 8))
