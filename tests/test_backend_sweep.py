"""Differential backend sweep + cost-model acceptance suite.

The ``bulk`` backend's whole-program sweep, the per-pass ``ref``/``pallas``
paths, and whatever ``auto`` picks must be BIT-identical on every plan the
planner can produce — padded and unpadded record counts, segment chains
stacked and unstacked, composite fallbacks and contradictions.  The cost
model may only ever choose which executor a wave lands on.

Also covered: calibration JSON round-trips and persistence, candidate
cutoff, decision memoization/factoring/stacking, and the backend-keyed
service warmup (an ``auto`` session pre-compiles every candidate backend
so a mid-traffic cost-model switch never stalls on jit).
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.db import BitmapDB, Column, Schema, col
from repro.engine import (backends, batch as engine_batch, bulk, costmodel,
                          planner)
from repro.engine.planner import And, Key, Not, Or, QueryPlan, key, plan

RNG = np.random.default_rng(20260807)

SWEEP_BACKENDS = ("ref", "bulk", "pallas")


def _random_pred(rng, m, depth):
    if depth == 0 or rng.random() < 0.3:
        leaf = key(int(rng.integers(0, m)))
        return ~leaf if rng.random() < 0.4 else leaf
    arity = int(rng.integers(2, 4))
    children = tuple(_random_pred(rng, m, depth - 1) for _ in range(arity))
    node = And(children) if rng.random() < 0.5 else Or(children)
    return ~node if rng.random() < 0.2 else node


def _packed(n, m, seed=7):
    rng = np.random.default_rng(seed)
    from repro.engine import policy
    nw = policy.num_words(n)
    packed = jnp.asarray(rng.integers(0, 2 ** 32, (m, nw), dtype=np.uint32))
    # leave tail bits arbitrary: the planner masks once, backends must not
    return packed


def _wave(seed, m, count, depth=3):
    rng = np.random.default_rng(seed)
    preds = [_random_pred(rng, m, depth) for _ in range(count)]
    # salt in a contradiction and a tautology-ish inversion
    preds.append(key(1) & ~key(1))
    preds.append(~(key(2) & ~key(2)))
    return preds


def _run_all(packed, preds, n, **kw):
    outs = {}
    for name in SWEEP_BACKENDS:
        outs[name] = engine_batch.execute_many(packed, preds,
                                               num_records=n,
                                               backend=name, **kw)
    outs["auto"] = engine_batch.execute_many(packed, preds, num_records=n,
                                             backend="auto", **kw)
    return outs


def _assert_identical(outs):
    r0, c0 = outs["ref"]
    for name, (r, c) in outs.items():
        np.testing.assert_array_equal(np.asarray(r), np.asarray(r0),
                                      err_msg=f"rows differ: {name}")
        np.testing.assert_array_equal(np.asarray(c), np.asarray(c0),
                                      err_msg=f"counts differ: {name}")


# ------------------------------------------------------- differential sweep
def test_bulk_backend_registered():
    assert "bulk" in backends.available_backends()
    b = backends.get_backend("bulk")
    assert b.run_program is not None


@pytest.mark.parametrize("n", [512, 1000, 37])   # aligned, unpadded, tiny
@pytest.mark.parametrize("seed", [11, 12])
def test_sweep_bit_identical_all_backends(n, seed):
    m = 24
    packed = _packed(n, m, seed)
    preds = _wave(seed, m, 12)
    _assert_identical(_run_all(packed, preds, n))


def test_sweep_bit_identical_factored_and_padded_output():
    n, m = 800, 16
    packed = _packed(n, m, 3)
    preds = _wave(3, m, 10)
    _assert_identical(_run_all(packed, preds, n, factor=True))
    _assert_identical(_run_all(packed, preds, n, pad_output=True))


def test_sweep_bit_identical_composite_fallback():
    n, m = 320, 12
    packed = _packed(n, m, 5)
    rng = np.random.default_rng(5)
    preds = [_random_pred(rng, m, 4) for _ in range(6)]
    # max_clauses=2 forces composite sub-plans for the wide trees
    outs = {name: engine_batch.execute_many(packed, preds, num_records=n,
                                            backend=name, max_clauses=2)
            for name in (*SWEEP_BACKENDS, "auto")}
    assert any(isinstance(pl, planner.CompositePlan)
               for pl in (planner.plan(p, max_clauses=2) for p in preds))
    _assert_identical(outs)


def _clean_packed(n, m, seed):
    """Packed segment with ZERO tail bits — the engine invariant durable
    segments carry (and ``append_packed``'s documented precondition)."""
    from repro.engine import policy
    raw = np.array(_packed(n, m, seed))
    pad = policy.num_words(n) * 32 - n
    if pad:
        raw[:, -1] &= np.uint32(0xFFFFFFFF >> pad)
    return jnp.asarray(raw)


@pytest.mark.parametrize("stack", [True, False, None])
def test_sweep_bit_identical_segments(stack):
    m = 20
    parts = [(_clean_packed(n, m, 40 + i), n)
             for i, n in enumerate((512, 370, 96))]
    n_total = sum(n for _, n in parts)
    preds = _wave(21, m, 8)
    ref_rows, ref_counts = engine_batch.execute_many_segments(
        parts, preds, backend="ref", stack_uniform=bool(stack))
    for name in ("bulk", "pallas", "auto"):
        rows, counts = engine_batch.execute_many_segments(
            parts, preds, backend=name, stack_uniform=stack)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(ref_rows))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(ref_counts))
    # and the segment chain agrees with the spliced monolith
    from repro.engine import runtime
    packed_all, n_acc = parts[0]
    for p, n in parts[1:]:
        packed_all = runtime.append_packed(packed_all, n_acc, p, n)
        n_acc += n
    rows2, counts2 = engine_batch.execute_many(packed_all, preds,
                                               num_records=n_total,
                                               backend="bulk")
    np.testing.assert_array_equal(np.asarray(rows2), np.asarray(ref_rows))
    np.testing.assert_array_equal(np.asarray(counts2),
                                  np.asarray(ref_counts))


def test_bulk_pallas_program_interpret_bit_identical():
    """The word-tiled Pallas realization of the bulk sweep (interpret mode
    off-TPU) matches the pure-jnp sweep on one lowered bucket."""
    n, m = 256, 10
    packed = _packed(n, m, 9)
    preds = _wave(9, m, 6)
    by_shape = {}
    for p in preds:
        pl = planner.plan(p)
        if not (isinstance(pl, QueryPlan) and pl.clauses):
            continue
        prog, shape, _, _ = engine_batch._lowered(pl)
        if shape is not None:
            by_shape.setdefault(shape, []).append(prog)
    shape, progs = max(by_shape.items(), key=lambda kv: len(kv[1]))
    sels, invs, post = engine_batch._bucket_arrays(progs, shape, m)
    sels, invs = jnp.asarray(sels), jnp.asarray(invs)
    post = jnp.asarray(post)
    aug = engine_batch._augmented(packed)
    want = bulk.run_program(aug, n, sels, invs, post)
    got = bulk.run_program_pallas(aug, n, sels, invs, post, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ------------------------------------------------------------- cost model
def _cal(bulk_wps=4e9, ref_wps=2e9, pallas_wps=5e5, copy=1e10,
         bulk_oh=5e-5, ref_oh=4e-5):
    return costmodel.Calibration((
        ("bulk", costmodel.BackendProfile(bulk_wps, bulk_oh)),
        ("pallas", costmodel.BackendProfile(pallas_wps, 2e-3)),
        ("ref", costmodel.BackendProfile(ref_wps, ref_oh)),
    ), copy, "cpu", "measured")


def test_calibration_json_roundtrip(tmp_path):
    cal = _cal()
    again = costmodel.Calibration.from_json(cal.to_json())
    assert again == cal
    p = costmodel.save_calibration(cal, str(tmp_path / "cal.json"))
    assert costmodel.load_calibration(p) == cal
    with open(p) as f:
        assert json.load(f)["version"] == costmodel.CALIBRATION_VERSION


def test_calibration_env_path_and_reset(tmp_path, monkeypatch):
    p = str(tmp_path / "cal.json")
    costmodel.save_calibration(_cal(bulk_wps=7.5e9), p)
    monkeypatch.setenv(costmodel.ENV_PATH, p)
    costmodel.set_calibration(None)          # drop the cached calibration
    try:
        got = costmodel.get_calibration()
        assert got.source == "measured"
        assert got.profile("bulk").words_per_sec == 7.5e9
    finally:
        monkeypatch.delenv(costmodel.ENV_PATH)
        costmodel.set_calibration(None)


def test_calibration_from_another_platform_is_refused(tmp_path, monkeypatch):
    p = str(tmp_path / "cal.json")
    foreign = dataclasses.replace(_cal(), platform="tpu")
    costmodel.save_calibration(foreign, p)
    monkeypatch.setenv(costmodel.ENV_PATH, p)
    costmodel.set_calibration(None)
    try:
        with pytest.raises(RuntimeError, match="measured on 'tpu'"):
            costmodel.get_calibration()
    finally:
        monkeypatch.delenv(costmodel.ENV_PATH)
        costmodel.set_calibration(None)


def test_unparseable_calibration_raises(tmp_path, monkeypatch):
    p = tmp_path / "cal.json"
    p.write_text("{not json")
    monkeypatch.setenv(costmodel.ENV_PATH, str(p))
    costmodel.set_calibration(None)
    try:
        with pytest.raises(ValueError):
            costmodel.get_calibration()
    finally:
        monkeypatch.delenv(costmodel.ENV_PATH)
        costmodel.set_calibration(None)


def test_platform_without_priors_raises(monkeypatch):
    monkeypatch.setattr(costmodel.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no cost-model priors"):
        costmodel._platform_default()


def test_candidates_cutoff_drops_interpreted_pallas():
    names = costmodel.candidates(_cal())
    assert "pallas" not in names             # 5e5 wps vs 4e9: way past 32x
    assert set(names) == {"bulk", "ref"}


def test_decide_picks_calibrated_fastest():
    preds = [plan(key(i) & ~key(i + 1)) for i in range(8)]
    fast_bulk = costmodel.decide(preds, num_words=1 << 14,
                                 cal=_cal(bulk_wps=8e9, ref_wps=1e9))
    assert fast_bulk.backend == "bulk"
    fast_ref = costmodel.decide(preds, num_words=1 << 14,
                                cal=_cal(bulk_wps=1e9, ref_wps=8e9))
    assert fast_ref.backend == "ref"
    assert dict(fast_ref.estimates)["ref"] < dict(fast_ref.estimates)["bulk"]
    assert fast_ref.terms["streamed_words"] > 0


def test_decide_memoizes_on_wave():
    preds = tuple(plan(key(i)) for i in range(4))
    cal = _cal()
    a = costmodel.decide(list(preds), num_words=4096, cal=cal)
    b = costmodel.decide(list(preds), num_words=4096, cal=cal)
    assert a is b                            # same cached Decision object
    c = costmodel.decide(list(preds), num_words=8192, cal=cal)
    assert c is not a


def test_decide_factoring_only_on_word_reduction():
    # many clauses sharing a 3-literal prefix: plain DNF streams one
    # wide group per clause; factoring hoists the prefix into one pass
    shared = key(0) & key(1) & key(2)
    wide = Or(tuple(shared & key(3 + i) for i in range(8)))
    preds = [plan(wide)]
    d = costmodel.decide(preds, num_words=1 << 14, cal=_cal())
    assert d.factor
    # single-clause plans: factoring can't help
    flat = [plan(key(i)) for i in range(6)]
    assert not costmodel.decide(flat, num_words=1 << 14, cal=_cal()).factor


def test_decide_stacking_tradeoff():
    preds = [plan(key(i % 8)) for i in range(16)]
    # huge dispatch overhead, fat copy pipe: stacking wins
    d = costmodel.decide(preds, num_words=256, num_segments=12, num_keys=32,
                         cal=_cal(bulk_oh=5e-3, ref_oh=5e-3, copy=1e12))
    assert d.stack_uniform
    # negligible overhead, starved copy pipe: stacking loses
    d2 = costmodel.decide(preds, num_words=256, num_segments=12,
                          num_keys=32,
                          cal=_cal(bulk_oh=1e-9, ref_oh=1e-9, copy=1e6))
    assert not d2.stack_uniform


def test_measure_calibration_tiny_smoke():
    cal = costmodel.measure_calibration(num_records=1 << 12, num_keys=16,
                                        num_queries=4, reps=1,
                                        backend_names=("ref", "bulk"),
                                        probe_seconds=10.0)
    assert cal.source == "measured"
    assert cal.copy_bytes_per_sec > 0
    for name in ("ref", "bulk"):
        prof = cal.profile(name)
        assert prof.words_per_sec > 0 and prof.dispatch_overhead_s > 0


# ------------------------------------------------- explain + warmup wiring
def _mk_db(n=512, m=16, backend="auto"):
    half = m // 2
    schema = Schema([Column.categorical("a", list(range(half))),
                     Column.categorical("b", list(range(half, m)))])
    rng = np.random.default_rng(0)
    db = BitmapDB(schema, backend=backend)
    db.append_encoded(np.stack([rng.integers(0, half, n, dtype=np.int32),
                                rng.integers(half, m, n, dtype=np.int32)],
                               axis=1))
    return db


def test_db_explain_surfaces_decision():
    db = _mk_db()
    q = (col("a") == 1) | ((col("a") == 2) & ~(col("b") == 9))
    ex = db.explain(q)
    assert ex["backend"] in backends.available_backends()
    assert ex["bucket_shape"] is not None
    assert ex["num_records"] == 512
    assert ex["est_matches"] is not None and ex["est_matches"] >= 0
    assert 0.0 <= ex["est_selectivity"] <= 1.0
    d = ex["decision"]
    assert d is not None and d["backend"] == ex["backend"]
    assert set(d["estimates"]) >= {"ref"}
    assert d["terms"]["streamed_words"] > 0
    # a pinned session reports its pinned backend, no decision
    db_ref = _mk_db(backend="ref")
    ex2 = db_ref.explain(q)
    assert ex2["backend"] == "ref" and ex2["decision"] is None
    # contradiction short-circuits
    ex3 = db.explain((col("a") == 1) & ~(col("a") == 1))
    assert ex3.get("fallback") == "contradiction"
    assert db.query((col("a") == 1) & ~(col("a") == 1)).count == 0


def test_service_warmup_is_backend_keyed():
    db_auto = _mk_db(backend="auto")
    db_ref = _mk_db(backend="ref")
    qs = [col("a") == 1, (col("a") == 2) & ~(col("b") == 9)]
    with db_auto.serve(max_batch=4, idle_after_ms=10_000.0) as svc:
        n_auto = svc.warmup(qs)
    with db_ref.serve(max_batch=4, idle_after_ms=10_000.0) as svc:
        n_ref = svc.warmup(qs)
    n_cands = len(costmodel.candidates())
    assert n_cands >= 2                      # bulk + ref at least, on CPU
    assert n_auto == n_ref * n_cands         # one warm pass per candidate


def test_auto_switch_mid_traffic_is_bit_exact():
    """Flipping the calibration (hence the chosen backend) between waves
    never changes result bits — the executor caches are backend-keyed."""
    db = _mk_db(n=700, backend="auto")
    q = [(col("a") == 1) | (col("b") == 9), ~(col("a") == 3)]
    try:
        costmodel.set_calibration(_cal(bulk_wps=9e9, ref_wps=1e9))
        r1, c1 = db.query_many(q).materialize()
        costmodel.set_calibration(_cal(bulk_wps=1e9, ref_wps=9e9))
        r2, c2 = db.query_many(q).materialize()
    finally:
        costmodel.set_calibration(None)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
