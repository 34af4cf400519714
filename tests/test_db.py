"""Acceptance suite for the `repro.db` facade.

Covers the schema/key-row mapping, the typed expression DSL (randomized
``expr -> Pred -> plan -> execute`` equivalence against a NumPy reference
evaluator over the encoded records), the legacy ``include=``/``exclude=``
deprecation shims (byte-identical results), lazy `Result` semantics, and
the end-to-end session lifecycle: schema ingest, streaming appends past
the spill threshold with ``path=``, crash recovery via ``repro.db.open``,
and a 1k-query mixed DSL batch served bit-identically to the raw
``engine.batch`` + `StoredIndex` path.
"""
import os
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

import repro
from repro.db import BitmapDB, Column, Schema, col
from repro.db import expr as expr_mod
from repro.engine import backends, batch as engine_batch, planner, policy
from repro.engine.planner import key
from repro.engine.runtime import StreamingIndexer


# ----------------------------------------------------------------- fixtures
def _weather_schema() -> Schema:
    return Schema([
        Column.categorical("city", ["SF", "NY", "LA"]),
        Column.binned("temp", edges=[-10.0, 0.0, 10.0, 20.0, 30.0, 45.0]),
        Column.categorical("tag", ["ok", "flagged", "dup"]),
    ])


def _weather_rows(rng, n):
    return {
        "city": [["SF", "NY", "LA"][i] for i in rng.integers(0, 3, n)],
        "temp": rng.uniform(-10, 45, n).tolist(),
        "tag": [["ok", "flagged", "dup"][i] for i in rng.integers(0, 3, n)],
    }


def _ref_eval(q, enc: np.ndarray, schema: Schema | None) -> np.ndarray:
    """NumPy reference semantics over encoded records: a leaf matches the
    records whose encoded words hit its lowered key set; combinators are
    boolean algebra.  Mirrors the DOCUMENTED bin-level semantics without
    touching planner, packing, or kernels."""
    if isinstance(q, planner.Key):
        return (enc == q.index).any(axis=1)
    if isinstance(q, (planner.Not, expr_mod.NotExpr)):
        return ~_ref_eval(q.child, enc, schema)
    if isinstance(q, (planner.And, expr_mod.AndExpr)):
        out = np.ones(enc.shape[0], bool)
        for c in q.children:
            out &= _ref_eval(c, enc, schema)
        return out
    if isinstance(q, (planner.Or, expr_mod.OrExpr)):
        out = np.zeros(enc.shape[0], bool)
        for c in q.children:
            out |= _ref_eval(c, enc, schema)
        return out
    if isinstance(q, expr_mod.Eq):
        keys = [schema.key_of(q.column, q.value)]
    elif isinstance(q, expr_mod.In):
        keys = [schema.key_of(q.column, v) for v in q.values]
    elif isinstance(q, expr_mod.Between):
        keys = list(schema[q.column].keys_between(q.lo, q.hi))
    else:
        raise TypeError(q)
    if not keys:
        return np.zeros(enc.shape[0], bool)
    return np.isin(enc, keys).any(axis=1)


# ------------------------------------------------------------------- schema
def test_schema_assigns_contiguous_key_rows():
    s = _weather_schema()
    assert s.num_keys == 3 + 5 + 3
    assert s.key_of("city", "SF") == 0
    assert s.key_of("city", "LA") == 2
    assert s.key_of("temp", -10.0) == 3       # first bin
    assert s.key_of("temp", 44.0) == 7        # last bin
    assert s.key_of("temp", 45.0) == 7        # right edge inclusive
    assert s.key_of("tag", "dup") == 10
    assert s.key_label(1) == "city='NY'"
    assert "temp" in s.key_label(4)


def test_schema_bin_boundaries():
    c = Schema([Column.binned("t", edges=[0, 10, 20, 30])])["t"]
    assert c.key_of(0) == 0 and c.key_of(9.99) == 0
    assert c.key_of(10) == 1 and c.key_of(29.9) == 2 and c.key_of(30) == 2
    with pytest.raises(KeyError):
        c.key_of(-0.01)
    with pytest.raises(KeyError):
        c.key_of(30.01)
    assert c.keys_between(-5, 5) == (0,)
    assert c.keys_between(5, 10) == (0, 1)     # 10 touches bin [10,20)
    assert c.keys_between(9.5, 25) == (0, 1, 2)
    assert c.keys_between(35, 40) == ()
    assert c.keys_between(-20, -11) == ()
    assert c.keys_between(30, 99) == (2,)      # right edge inclusive


def test_schema_encode_column_and_row_major():
    s = _weather_schema()
    cm = s.encode({"city": ["SF", "LA"], "temp": [5.0, 25.0],
                   "tag": ["ok", "dup"]})
    rm = s.encode([{"city": "SF", "temp": 5.0, "tag": "ok"},
                   {"city": "LA", "temp": 25.0, "tag": "dup"}])
    np.testing.assert_array_equal(cm, rm)
    np.testing.assert_array_equal(cm, [[0, 4, 8], [2, 6, 10]])
    with pytest.raises(KeyError, match="missing column"):
        s.encode({"city": ["SF"], "temp": [5.0]})
    with pytest.raises(KeyError, match="unknown columns"):
        s.encode({"city": ["SF"], "temp": [5.0], "tag": ["ok"],
                  "extra": [1]})
    with pytest.raises(KeyError):
        s.encode({"city": ["Atlantis"], "temp": [5.0], "tag": ["ok"]})


@pytest.mark.parametrize("dtype", [np.uint8, np.int16, np.int64])
def test_schema_encode_integer_arrays_match_per_value_path(dtype):
    """Integer NumPy columns of a categorical encode through a lookup
    table: same key ids as value-by-value encoding, same KeyError on a
    value the column lacks."""
    s = Schema([Column.categorical("a", [3, 5, 7, 11]),
                Column.categorical("b", range(-2, 6))])
    rng = np.random.default_rng(4)
    a = rng.choice([3, 5, 7, 11], 500).astype(dtype)
    b = rng.integers(0 if dtype == np.uint8 else -2, 6, 500).astype(dtype)
    fast = s.encode({"a": a, "b": b})
    slow = s.encode({"a": a.tolist(), "b": b.tolist()})
    np.testing.assert_array_equal(fast, slow)
    with pytest.raises(KeyError, match="has no value 4"):
        s.encode({"a": np.array([3, 4, 5], dtype), "b": b[:3]})


def test_session_pinned_to_a_device_answers_the_same():
    """``device=`` commits the session's index to that device; answers
    equal an unpinned session's."""
    import jax
    dev = jax.devices()[0]
    rows = _weather_rows(np.random.default_rng(2), 300)
    pinned = BitmapDB(_weather_schema(), device=dev)
    plain = BitmapDB(_weather_schema())
    pinned.append(rows)
    plain.append(rows)
    buf, _ = pinned.indexer.view()
    assert buf.committed and buf.devices() == {dev}
    q = (col("city") == "SF") & ~(col("tag") == "dup")
    assert pinned.query(q).count == plain.query(q).count
    np.testing.assert_array_equal(pinned.query(q).ids, plain.query(q).ids)


def test_schema_validation_errors():
    with pytest.raises(ValueError, match="duplicate column"):
        Schema([Column.categorical("a", [1]), Column.categorical("a", [2])])
    with pytest.raises(ValueError, match="duplicate values"):
        Column.categorical("a", [1, 1])
    with pytest.raises(ValueError, match="ascending"):
        Column.binned("t", edges=[0, 0, 10])
    with pytest.raises(ValueError, match="at least one column"):
        Schema([])


def test_schema_json_round_trip():
    s = _weather_schema()
    s2 = Schema.from_json(s.to_json())
    assert s2 == s and s2.num_keys == s.num_keys
    assert s2.key_of("temp", 15.0) == s.key_of("temp", 15.0)


def test_schema_count_keys_exact():
    s = _weather_schema()
    rng = np.random.default_rng(0)
    rows = _weather_rows(rng, 300)
    enc = s.encode(rows)
    counts = s.count_keys(enc)
    assert counts.sum() == 300 * 3            # one word per column
    assert counts[0] == rows["city"].count("SF")


# ---------------------------------------------------------------------- DSL
def test_expr_lowering_shapes():
    s = _weather_schema()
    assert expr_mod.lower(col("city") == "SF", s) == key(0)
    assert expr_mod.lower(col("city") != "SF", s) == ~key(0)
    low = expr_mod.lower(col("city").isin(["SF", "NY"]), s)
    assert isinstance(low, planner.Or)
    assert expr_mod.lower(col("city").isin(["SF"]), s) == key(0)
    # empty isin is a provable contradiction: zero clauses, zero passes
    pl = planner.plan(expr_mod.lower(col("city").isin([]), s))
    assert pl.clauses == ()
    # between lowers to the overlapping bins
    low = expr_mod.lower(col("temp").between(5, 25), s)
    assert {p.index for p in low.children} == {4, 5, 6}
    # comparison sugar
    low = expr_mod.lower(col("temp") >= 30.0, s)
    assert low == key(7)
    low = expr_mod.lower(col("temp") < 0.0, s)
    assert low == key(3)


def test_expr_mixed_raw_pred_trees():
    s = _weather_schema()
    mixed = key(3) & (col("city") == "NY")
    low = expr_mod.lower(mixed, s)
    assert low == planner.And((key(3), key(1)))
    # and the planner accepts the lowered result
    assert planner.plan(low).num_passes == 1


def test_expr_errors():
    s = _weather_schema()
    with pytest.raises(TypeError, match="column-to-column"):
        col("a") == col("b")
    with pytest.raises(KeyError, match="no column"):
        expr_mod.lower(col("nope") == 1, s)
    with pytest.raises(ValueError, match="need a Schema"):
        expr_mod.lower(col("city") == "SF", None)
    with pytest.raises(TypeError, match="combine an expression"):
        (col("city") == "SF") & "flagged"
    # raw predicates lower fine without a schema
    assert expr_mod.lower(key(1) & ~key(2), None) == key(1) & ~key(2)


def _random_expr(rng, schema: Schema, depth: int):
    if depth == 0 or rng.random() < 0.35:
        c = schema.columns[rng.integers(0, len(schema.columns))]
        kind = rng.integers(0, 4)
        if c.kind == "categorical":
            vals = list(c.values)
            if kind == 0:
                return col(c.name) == vals[rng.integers(0, len(vals))]
            if kind == 1:
                k = int(rng.integers(0, len(vals) + 1))
                pick = list(rng.choice(len(vals), size=k, replace=False))
                return col(c.name).isin([vals[i] for i in pick])
            if kind == 2:
                return col(c.name) != vals[rng.integers(0, len(vals))]
            return planner.key(int(rng.integers(0, schema.num_keys)))
        lo_e, hi_e = c.edges[0], c.edges[-1]
        if kind == 0:
            return col(c.name) == float(rng.uniform(lo_e, hi_e))
        if kind == 1:
            a, b = sorted(rng.uniform(lo_e - 5, hi_e + 5, 2))
            return col(c.name).between(float(a), float(b))
        if kind == 2:
            return col(c.name) >= float(rng.uniform(lo_e, hi_e))
        return col(c.name) < float(rng.uniform(lo_e, hi_e))
    arity = int(rng.integers(2, 4))
    children = [_random_expr(rng, schema, depth - 1) for _ in range(arity)]
    out = children[0]
    for c in children[1:]:
        out = (out & c) if rng.random() < 0.5 else (out | c)
    return ~out if rng.random() < 0.25 else out


@pytest.mark.parametrize("seed", range(6))
def test_random_exprs_match_numpy_reference(seed):
    """The DSL acceptance property: expr -> Pred -> plan -> packed
    execution == NumPy reference evaluation over the encoded records, for
    random schemas, data, and expression trees."""
    rng = np.random.default_rng(seed)
    cols = [Column.categorical("a", list(range(int(rng.integers(2, 6))))),
            Column.binned("b", edges=sorted(
                set(rng.uniform(-50, 50, int(rng.integers(3, 7)))))),
            Column.categorical("c", ["x", "y", "z", "w"])]
    schema = Schema(cols[: int(rng.integers(2, 4))])
    n = int(rng.integers(40, 220))
    rows = {}
    for c in schema.columns:
        if c.kind == "categorical":
            vals = list(c.values)
            rows[c.name] = [vals[i]
                            for i in rng.integers(0, len(vals), n)]
        else:
            rows[c.name] = rng.uniform(c.edges[0], c.edges[-1], n).tolist()
    db = BitmapDB(schema, backend="ref")
    db.ingest(rows)
    enc = schema.encode(rows)
    exprs = [_random_expr(rng, schema, depth=int(rng.integers(0, 3)))
             for _ in range(12)]
    results = db.query_many(exprs)
    for q, res in zip(exprs, results):
        want = np.flatnonzero(_ref_eval(q, enc, schema))
        np.testing.assert_array_equal(res.ids, want), q
        assert res.count == len(want)


# ------------------------------------------------------------ legacy shims
def test_include_exclude_shim_byte_identical():
    """The deprecated key-list surface must produce byte-identical results
    to what those callers always got from the planner directly."""
    rng = np.random.default_rng(3)
    records = jnp.asarray(rng.integers(0, 24, (77, 6), dtype=np.int32))
    keys = jnp.arange(24, dtype=jnp.int32)
    packed = backends.get_backend("ref").create_index(records, keys)
    bi = policy.BitmapIndex(packed, 77)
    from repro.core.bic import BICCore, BICConfig
    core = BICCore(BICConfig(num_keys=24, num_records=77,
                             words_per_record=6, backend="ref"))
    with pytest.warns(DeprecationWarning, match="include=/exclude="):
        r1, c1 = core.query(bi, include=[2, 4], exclude=[5])
    r2, c2 = planner.execute(
        packed, planner.from_include_exclude([2, 4], [5]),
        num_records=77, backend="ref")
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(c1) == int(c2)


def test_pipeline_include_exclude_shim_byte_identical(tmp_path):
    from repro.data.pipeline import BitmapIndexedDataset, DataConfig
    cfg = DataConfig(vocab_size=64, seq_len=8, docs_per_shard=64,
                     num_shards=1, num_attributes=32)
    ds = BitmapIndexedDataset(cfg)
    with pytest.warns(DeprecationWarning):
        legacy = ds.select(0, include=[9], exclude=[20])
    modern = ds.select(0, where=key(9) & ~key(20))
    np.testing.assert_array_equal(legacy, modern)
    # and the DSL agrees with the raw key rows it maps onto
    dsl = ds.select(0, where=(col("lang") == 1) & ~(col("quality") == 4))
    raw = ds.select(0, where=key(9) & ~key(20))
    np.testing.assert_array_equal(dsl, raw)


# ------------------------------------------------------------- lazy results
def test_results_are_lazy_and_snapshot_query_time():
    s = _weather_schema()
    rng = np.random.default_rng(4)
    db = BitmapDB(s, backend="ref")
    db.ingest(_weather_rows(rng, 96))
    calls = []
    res = db.query(col("city") == "SF")
    assert not res._batch.executed
    n0 = res.count                       # materializes ONCE for the batch
    assert res._batch.executed
    db.append(_weather_rows(rng, 32))    # later append
    assert res.count == n0               # cached
    res2 = db.query(col("city") == "SF")
    assert res2.count >= n0 and db.num_records == 128
    del calls


def test_query_many_shares_one_batch():
    s = _weather_schema()
    db = BitmapDB(s, backend="ref")
    db.ingest(_weather_rows(np.random.default_rng(5), 64))
    rs = db.query_many([col("city") == "SF", col("tag") == "ok",
                        col("temp") >= 20.0])
    assert rs[0]._batch is rs[1]._batch is rs[2]._batch
    _ = rs[2].ids
    assert rs[0]._batch.executed


# ------------------------------------------------------------ session modes
def test_read_only_session_rejects_appends():
    s = _weather_schema()
    db = BitmapDB(s, backend="ref")
    db.ingest(_weather_rows(np.random.default_rng(6), 40))
    ro = BitmapDB.from_index(db.index, s, backend="ref")
    with pytest.raises(RuntimeError, match="read-only"):
        ro.append(_weather_rows(np.random.default_rng(7), 4))
    assert ro.query(col("city") == "SF").count == \
        db.query(col("city") == "SF").count
    # read-only stats popcount exactly
    assert ro.stats.counts == db.stats.counts


def test_constructor_and_open_errors(tmp_path):
    s = _weather_schema()
    with pytest.raises(ValueError, match="needs a Schema"):
        BitmapDB()
    with pytest.raises(ValueError, match="contradicts the schema"):
        BitmapDB(s, num_keys=5)
    p = os.path.join(str(tmp_path), "idx")
    db = BitmapDB(s, path=p, backend="ref", spill_records=None)
    db.ingest(_weather_rows(np.random.default_rng(8), 16))
    db.snapshot()
    with pytest.raises(ValueError, match="repro.db.open"):
        BitmapDB(s, path=p, backend="ref")
    with pytest.raises(ValueError, match="different schema"):
        BitmapDB.open(p, Schema([Column.categorical("other", [1])]),
                      backend="ref")
    with pytest.raises(FileNotFoundError, match="SCHEMA.json"):
        BitmapDB.open(os.path.join(str(tmp_path), "empty"), backend="ref")
    # schema persisted: open() without schema= recovers it
    db2 = repro.open(p, backend="ref")
    assert db2.schema == s and db2.num_records == 16


def test_top_level_lazy_exports():
    import repro as r
    assert r.BitmapDB is BitmapDB
    assert r.Schema is Schema and r.Column is Column
    assert r.col is col
    assert callable(r.open)
    assert "BitmapDB" in dir(r) and "engine" in dir(r)
    with pytest.raises(AttributeError):
        r.not_a_symbol


# ----------------------------------------------------- end-to-end acceptance
def _mixed_dsl_queries(schema: Schema, count: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    cities = list(schema["city"].values)
    tags = list(schema["tag"].values)
    out = []
    for i in range(count):
        fam = i % 7
        city = cities[rng.integers(0, len(cities))]
        tag = tags[rng.integers(0, len(tags))]
        lo, hi = sorted(rng.uniform(-10, 45, 2))
        if fam == 0:
            q = col("city") == city
        elif fam == 1:
            q = (col("city") == city) & ~(col("tag") == tag)
        elif fam == 2:
            q = col("temp").between(float(lo), float(hi))
        elif fam == 3:
            q = col("city").isin([city, cities[0]]) & (col("tag") == tag)
        elif fam == 4:
            q = (col("temp") >= float(lo)) & ~(col("city") == city)
        elif fam == 5:
            q = planner.key(int(rng.integers(0, schema.num_keys)))
        else:
            q = ((col("city") == city) & (col("tag") == tag)) | \
                (col("temp") < float(lo))
        out.append(q)
    return out


def test_bitmapdb_end_to_end_acceptance(tmp_path):
    """ISSUE acceptance: ingest with a Schema, stream appends past the
    spill threshold with path=, crash-recover via repro.db.open(), serve a
    1k-query mixed DSL batch — bit-identical to the raw engine.batch +
    StoredIndex path."""
    from repro.store import SegmentStore, open_index

    schema = _weather_schema()
    rng = np.random.default_rng(11)
    path = os.path.join(str(tmp_path), "db")
    db = BitmapDB(schema, path=path, backend="ref", spill_records=256)
    total = 0
    encoded_blocks = []
    for blk in (200, 150, 300, 90, 60):      # crosses the threshold twice
                                             # and leaves a 150-record tail
        rows_blk = _weather_rows(rng, blk)
        encoded_blocks.append(schema.encode(rows_blk))
        db.append(rows_blk)
        total += blk
    enc_all = np.concatenate(encoded_blocks)
    assert db.num_records == total
    store = db.store
    assert store.durable_records >= 256            # spilled segments
    assert store.durable_records < total           # and a live WAL tail
    live_packed = np.asarray(db.index.packed)

    # ---- crash: reopen from disk only -------------------------------
    rec = repro.open(path, backend="ref")
    assert rec.num_records == total
    np.testing.assert_array_equal(np.asarray(rec.index.packed), live_packed)

    # ---- serve a 1k mixed DSL batch through the facade ---------------
    queries = _mixed_dsl_queries(schema, 1000, seed=12)
    step = rec.serve_step()
    rows, counts = step(queries)
    assert rows.shape[0] == 1000

    # ---- raw path 1: engine.batch over the recovered contiguous index
    plans = [planner.plan(expr_mod.lower(q, schema)) for q in queries]
    want_r, want_c = engine_batch.execute_many(
        rec.index.packed, plans, num_records=total, backend="ref")
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_r))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_c))

    # ---- raw path 2: StoredIndex (segments + extracted WAL tail) -----
    st2 = SegmentStore(path)
    si = StreamingIndexer.restore(st2, jnp.arange(schema.num_keys,
                                                  dtype=jnp.int32),
                                  backend="ref")
    tail_n = si.num_records - st2.durable_records
    tail = (policy.extract_packed(si.index.packed, st2.durable_records,
                                  tail_n), tail_n)
    stored = open_index(st2, tail=tail if tail_n else None)
    sr, sc = stored.query_many(plans, backend="ref")
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(sc))

    # ---- and the numpy-reference ground truth ------------------------
    res = rec.query_many(queries[:50])
    for q, r in zip(queries[:50], res):
        want = np.flatnonzero(_ref_eval(q, enc_all, schema))
        np.testing.assert_array_equal(r.ids, want)


def test_stats_feed_clause_ordering():
    """A live session's plans order DNF clauses by the ingested data's
    selectivity, and results stay identical to unordered planning."""
    s = Schema([Column.categorical("a", [0, 1]),
                Column.categorical("b", [0, 1]),
                Column.categorical("c", [0, 1, 2])])
    # skew: a==1 is rare, b==1 is common
    rows = {"a": [1] * 5 + [0] * 95,
            "b": [1] * 90 + [0] * 10,
            "c": ([0, 1, 2] * 34)[:100]}
    db = BitmapDB(s, backend="ref")
    db.ingest(rows)
    q = ((col("b") == 1) & (col("c") == 0)) | ((col("a") == 1) &
                                              (col("c") == 1))
    pl_db = db._plan_for(q)
    pred = expr_mod.lower(q, s)
    pl_plain = planner.plan(pred)
    assert set(pl_db.clauses) == set(pl_plain.clauses)
    # the rare-key clause (a==1 ~ 5 records) must come first under stats
    first = pl_db.clauses[0]
    assert (s.key_of("a", 1), False) in first
    r1 = db.query(q)
    r2, c2 = planner.execute(db.index.packed, pl_plain,
                             num_records=100, backend="ref")
    np.testing.assert_array_equal(np.asarray(r1.rows), np.asarray(r2))
    assert r1.count == int(c2)
