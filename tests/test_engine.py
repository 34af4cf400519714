"""repro.engine: backend registry, boolean query planner, streaming runtime.

The acceptance bar for the engine layer:
  * ``execute(plan)`` on a random predicate tree is bit-identical between
    the ``pallas`` (interpret) and ``ref`` backends;
  * incremental append matches a from-scratch rebuild of the same records;
  * the planner's DNF normalization preserves boolean semantics (checked
    against dense evaluation) including non-32-aligned N and M and
    all-inverted clauses (the kernel pad-guard path).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.bic import BICConfig, BICCore
from repro.engine import backends, batch, planner, policy, runtime
from repro.engine.planner import (And, CompositePlan, Key, Not, Or,
                                  QueryPlan, evaluate_dense, execute, factor,
                                  from_include_exclude, key, plan,
                                  total_clauses)
from repro.engine.runtime import (MulticoreRuntime, StreamingIndexer,
                                  append_packed, fold_block_indexes,
                                  multicore_create_index)
from repro.kernels import ref

RNG = np.random.default_rng(2024)


def _random_index(n, m, w=8, lo=0, hi=48):
    records = jnp.asarray(RNG.integers(lo, hi, (n, w), dtype=np.int32))
    keys = jnp.asarray(RNG.integers(lo, hi, (m,), dtype=np.int32))
    return records, keys


def _random_pred(rng, m, depth):
    """Random nested AND/OR/NOT tree over key indices < m."""
    if depth == 0 or rng.random() < 0.3:
        leaf = key(int(rng.integers(0, m)))
        return ~leaf if rng.random() < 0.4 else leaf
    arity = int(rng.integers(2, 4))
    children = tuple(_random_pred(rng, m, depth - 1) for _ in range(arity))
    node = And(children) if rng.random() < 0.5 else Or(children)
    return ~node if rng.random() < 0.2 else node


# ------------------------------------------------------------ backend layer
def test_backend_registry_and_resolution():
    assert set(backends.available_backends()) >= {"pallas", "ref", "auto"}
    assert backends.resolve_backend("ref") == "ref"
    assert backends.resolve_backend("auto") in ("pallas", "ref")
    with pytest.raises(ValueError):
        backends.resolve_backend("no-such-backend")


@pytest.mark.parametrize("n,m,w", [(16, 8, 32), (19, 37, 7), (50, 5, 3),
                                   (33, 64, 8)])
def test_backends_create_bit_identical(n, m, w):
    records, keys = _random_index(n, m, w)
    a = backends.get_backend("pallas").create_index(records, keys)
    b = backends.get_backend("ref").create_index(records, keys)
    assert a.shape == (m, policy.num_words(n))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- planner: DNF
def test_plan_normalizes_de_morgan():
    p = ~(key(1) | key(2))                 # -> ~1 & ~2, one fused pass
    assert plan(p).clauses == (((1, True), (2, True)),)


def test_plan_drops_contradictions():
    assert plan(key(3) & ~key(3)).clauses == ()
    # contradiction inside one branch of an OR leaves the other branch
    assert plan((key(3) & ~key(3)) | key(1)).clauses == (((1, False),),)


def test_plan_absorption_and_dedup():
    # a | (a & b) -> a ;  duplicate literals collapse
    assert plan(key(1) | (key(1) & key(2))).clauses == (((1, False),),)
    assert plan(key(4) & key(4)).clauses == (((4, False),),)


def test_plan_shape_is_cache_key():
    a = plan((key(1) | key(2)) & key(3))
    b = plan((key(5) | key(6)) & key(7))
    assert a.shape == b.shape == (2, 2)
    assert a.clauses != b.clauses


def test_plan_clauses_ordered_cheapest_first():
    """Satellite: DNF clauses order by literal count (cheapest pass first,
    short-circuit potential for composite executors) — and since the plan
    is an OR of clauses, the ordering never changes a result bit."""
    p = key(9) | (key(1) & key(2) & key(3)) | (key(4) & key(5))
    pl = plan(p)
    assert pl.shape == (1, 2, 3)
    assert pl.shape == tuple(sorted(pl.shape))
    records, keys = _random_index(70, 12)
    idx = backends.get_backend("ref").create_index(records, keys)
    r1, c1 = execute(idx, pl, num_records=70, backend="ref")
    r2, c2 = execute(idx, QueryPlan(tuple(reversed(pl.clauses))),
                     num_records=70, backend="ref")
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(c1) == int(c2)


def test_plan_stats_reorder_clauses_identical_bits():
    """Satellite: per-key set-bit stats order DNF clauses by estimated
    selectivity (literal count stays the uninformed fallback), and the
    reordered passes produce identical result bits."""
    p = (key(0) & key(1)) | (key(2) & key(3) & key(4)) | key(5)
    baseline = plan(p)
    assert [len(c) for c in baseline.clauses] == [1, 2, 3]
    n = 70
    # key 5 saturated, keys 2-4 rare: the stats must push the 3-literal
    # clause first and the single-literal clause last
    counts = [60, 60, 2, 2, 2, 70] + [35] * 6
    stats = planner.KeyStats.from_counts(counts, n)
    assert stats.literal_estimate(5, False) == 70
    assert stats.literal_estimate(5, True) == 0
    assert stats.literal_estimate(99, False) == n     # unknown key
    ordered = plan(p, stats=stats)
    assert set(ordered.clauses) == set(baseline.clauses)
    assert [len(c) for c in ordered.clauses] == [3, 2, 1]
    records, keys = _random_index(n, 12)
    idx = backends.get_backend("ref").create_index(records, keys)
    r1, c1 = execute(idx, baseline, num_records=n, backend="ref")
    r2, c2 = execute(idx, ordered, num_records=n, backend="ref")
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(c1) == int(c2)
    # batched serving agrees too (plans bucket independently of order)
    rows, cts = batch.execute_many(idx, [baseline, ordered],
                                   num_records=n, backend="ref")
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(rows[1]))


def test_include_exclude_compiles_to_single_pass():
    p = from_include_exclude([2, 4], [5])
    assert plan(p).clauses == (((2, False), (4, False), (5, True)),)
    with pytest.raises(ValueError):
        from_include_exclude([], [])


# ------------------------------------------- planner: differential execution
@pytest.mark.parametrize("n,m", [(32, 32), (19, 37), (50, 5), (200, 12)])
def test_random_trees_pallas_vs_ref_bit_identical(n, m):
    """Acceptance: random predicate trees, non-32-aligned N and M, identical
    packed result and count across backends, both matching dense eval."""
    records, keys = _random_index(n, m)
    idx = backends.get_backend("ref").create_index(records, keys)
    dense = ref.unpack_bits(idx, n)
    rng = np.random.default_rng(n * 1000 + m)
    for _ in range(8):
        pred = _random_pred(rng, m, depth=3)
        r_ref, c_ref = execute(idx, pred, num_records=n, backend="ref")
        r_pal, c_pal = execute(idx, pred, num_records=n, backend="pallas")
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_pal))
        assert int(c_ref) == int(c_pal)
        want = np.asarray(evaluate_dense(pred, dense))
        got = np.asarray(ref.unpack_bits(r_ref[None], n))[0].astype(bool)
        np.testing.assert_array_equal(got, want)
        assert int(c_ref) == int(want.sum())


def test_all_inverted_operands_hit_pad_guard():
    """Every operand inverted + non-aligned N: inverted rows turn the pad
    words all-ones; the kernel pad-guard must zero them again."""
    n, m = 45, 6
    records, keys = _random_index(n, m)
    idx = backends.get_backend("ref").create_index(records, keys)
    pred = And(tuple(~key(i) for i in range(m)))
    for backend in ("ref", "pallas"):
        row, cnt = execute(idx, pred, num_records=n, backend=backend)
        want = np.asarray(evaluate_dense(pred, ref.unpack_bits(idx, n)))
        got = np.asarray(ref.unpack_bits(row[None], n))[0].astype(bool)
        np.testing.assert_array_equal(got, want)
        assert int(cnt) == int(want.sum())
        # tail bits past n must be zero even though every operand inverted
        tail = np.asarray(ref.unpack_bits(row[None], row.shape[0] * 32))[0]
        assert tail[n:].sum() == 0


def test_out_of_range_key_raises():
    """A typo'd key id must raise, not silently gather-clamp to the last
    index row."""
    records, keys = _random_index(40, 4)
    idx = backends.get_backend("ref").create_index(records, keys)
    with pytest.raises(ValueError, match=r"\[99\] out of range"):
        execute(idx, key(99), num_records=40)
    with pytest.raises(ValueError, match="out of range"):
        execute(idx, key(0) & ~key(-1), num_records=40)
    # a typo buried in a branch normalization simplifies away still raises
    with pytest.raises(ValueError, match=r"\[99\] out of range"):
        execute(idx, (key(99) & ~key(99)) | key(1), num_records=40)
    with pytest.raises(ValueError, match=r"\[99\] out of range"):
        execute(idx, key(1) | (key(1) & key(99)), num_records=40)


def test_contradiction_executes_without_kernel_pass():
    records, keys = _random_index(40, 4)
    idx = backends.get_backend("ref").create_index(records, keys)
    row, cnt = execute(idx, key(0) & ~key(0), num_records=40)
    assert int(cnt) == 0
    assert np.asarray(row).sum() == 0


def test_executor_jit_cache_reuses_same_shape():
    records, keys = _random_index(64, 16)
    idx = backends.get_backend("ref").create_index(records, keys)
    before = planner.compiled_plan_cache_info().currsize
    execute(idx, (key(1) | key(2)) & key(3), num_records=64, backend="ref")
    mid = planner.compiled_plan_cache_info()
    # same plan shape, different key ids -> cache hit, no new executor
    execute(idx, (key(9) | key(4)) & key(7), num_records=64, backend="ref")
    after = planner.compiled_plan_cache_info()
    assert mid.currsize >= before
    assert after.currsize == mid.currsize
    assert after.hits > mid.hits


def test_biccore_query_where_matches_include_exclude():
    records, keys = _random_index(30, 8)
    core = BICCore(BICConfig(num_keys=8, num_records=30, words_per_record=8,
                             backend="ref"))
    bi = core.create(records, keys)
    r1, c1 = core.query(bi, include=[2, 4], exclude=[5])
    r2, c2 = core.query(bi, where=key(2) & key(4) & ~key(5))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(c1) == int(c2)
    with pytest.raises(ValueError):
        core.query(bi, include=[1], where=key(1))


# ------------------------------------------------- planner: size guard
def _alternating_deep_tree(levels: int, m: int):
    """AND-of-OR alternation ``levels`` deep: full DNF distribution would
    produce 2**levels clauses."""
    p = Or((key(0 % m), key(1 % m)))
    for i in range(1, levels):
        p = And((Or((key(2 * i % m), key((2 * i + 1) % m))), p))
    return p


def test_plan_size_guard_bounds_adversarial_trees():
    """Acceptance: a 20-level alternating OR/AND tree (2**20 DNF clauses)
    plans as a composite of sub-plans, each under the clause ceiling."""
    ceiling = 64
    pred = _alternating_deep_tree(20, m=64)
    pl = plan(pred, max_clauses=ceiling)
    assert isinstance(pl, CompositePlan)

    def leaves(node):
        if isinstance(node, QueryPlan):
            return [node]
        return [leaf for part in node.parts for leaf in leaves(part)]

    assert all(len(leaf.clauses) <= ceiling for leaf in leaves(pl))
    # nowhere near the 2**20 clauses full distribution would produce
    assert total_clauses(pl) <= ceiling + 2 * 20


def test_plan_size_guard_preserves_semantics():
    n, m = 50, 64
    records, keys = _random_index(n, m)
    idx = backends.get_backend("ref").create_index(records, keys)
    dense = ref.unpack_bits(idx, n)
    pred = _alternating_deep_tree(20, m=m)
    pl = plan(pred, max_clauses=16)
    assert isinstance(pl, CompositePlan)
    row, cnt = execute(idx, pl, num_records=n, backend="ref")
    want = np.asarray(evaluate_dense(pred, dense))
    got = np.asarray(ref.unpack_bits(row[None], n))[0].astype(bool)
    np.testing.assert_array_equal(got, want)
    assert int(cnt) == int(want.sum())
    # small trees stay plain QueryPlans under the default guard
    assert isinstance(plan((key(1) | key(2)) & key(3)), QueryPlan)


def test_plan_guard_disabled_distributes_fully():
    pred = _alternating_deep_tree(8, m=32)          # 256 clauses, tractable
    pl = plan(pred, max_clauses=None)
    assert isinstance(pl, QueryPlan)
    assert len(pl.clauses) == 2 ** 8


# ------------------------------------------------- planner: clause factoring
def test_factor_shares_common_clause_prefix():
    # (a&b&c) | (a&b&d) | (a&b&e) -> a&b & (c|d|e): 2 passes instead of 3
    p = ((key(1) & key(2) & key(3)) | (key(1) & key(2) & key(4))
         | (key(1) & key(2) & key(5)))
    qp = plan(p)
    fp = factor(qp)
    assert qp.num_passes == 3
    assert fp.num_passes == 2
    assert fp.groups == ((((1, False), (2, False)),
                          ((3, False), (4, False), (5, False))),)


def test_factor_collapses_pure_or_to_one_pass():
    # a|b|c = ~(~a & ~b & ~c): one De-Morgan pass instead of three
    fp = factor(plan(key(1) | key(2) | key(3)))
    assert fp.num_passes == 1
    assert fp.groups == (((), ((1, False), (2, False), (3, False))),)


def test_factor_passes_through_unrelated_clauses():
    fp = factor(plan((key(1) & key(2)) | (key(3) & key(4))))
    assert fp.num_passes == 2           # nothing shared: plain passes
    assert all(d == () for _, d in fp.groups)


@pytest.mark.parametrize("n,m", [(50, 12), (19, 37)])
def test_factored_execution_bit_identical(n, m):
    records, keys = _random_index(n, m)
    idx = backends.get_backend("ref").create_index(records, keys)
    rng = np.random.default_rng(n * 77 + m)
    checked = 0
    for _ in range(10):
        pred = _random_pred(rng, m, depth=3)
        pl = plan(pred)
        if not isinstance(pl, planner.QueryPlan) or not pl.clauses:
            continue
        checked += 1
        r1, c1 = execute(idx, pl, num_records=n, backend="ref")
        r2, c2 = execute(idx, factor(pl), num_records=n, backend="ref")
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        assert int(c1) == int(c2)
    assert checked >= 5


def test_factored_execution_pallas_matches_ref():
    records, keys = _random_index(40, 9)
    idx = backends.get_backend("ref").create_index(records, keys)
    fp = factor(plan((key(0) & key(1)) | (key(0) & key(2)) | key(3)
                     | key(4)))
    r_ref, c_ref = execute(idx, fp, num_records=40, backend="ref")
    r_pal, c_pal = execute(idx, fp, num_records=40, backend="pallas")
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_pal))
    assert int(c_ref) == int(c_pal)


def test_plan_constants_are_cached():
    records, keys = _random_index(64, 16)
    idx = backends.get_backend("ref").create_index(records, keys)
    pl = plan((key(1) | key(2)) & key(3))
    execute(idx, pl, num_records=64, backend="ref")
    before = planner.plan_constant_cache_info()
    for _ in range(3):
        execute(idx, pl, num_records=64, backend="ref")
    after = planner.plan_constant_cache_info()
    assert after.hits >= before.hits + 3    # no per-call literal re-upload
    assert after.currsize == before.currsize


# --------------------------------------------------- batched query serving
def test_execute_many_matches_sequential_execute():
    """Acceptance: a mixed batch (random trees + contradiction + deep
    composite + include/exclude) is bit-identical to per-query execute."""
    n, m = 200, 24
    records, keys = _random_index(n, m)
    idx = backends.get_backend("ref").create_index(records, keys)
    rng = np.random.default_rng(99)
    preds = [_random_pred(rng, m, depth=3) for _ in range(30)]
    preds.append(key(0) & ~key(0))                    # contradiction
    preds.append(from_include_exclude([2, 4], [5]))
    preds.append(_alternating_deep_tree(15, m=m))     # composite fallback
    for factor_flag in (False, True):
        rows, counts = batch.execute_many(idx, preds, num_records=n,
                                          backend="ref", factor=factor_flag)
        assert rows.shape == (len(preds), policy.num_words(n))
        for i, p in enumerate(preds):
            r, c = execute(idx, p, num_records=n, backend="ref")
            np.testing.assert_array_equal(np.asarray(rows[i]),
                                          np.asarray(r))
            assert int(counts[i]) == int(c)


def test_execute_many_pallas_matches_ref():
    n, m = 50, 10
    records, keys = _random_index(n, m)
    idx = backends.get_backend("ref").create_index(records, keys)
    preds = [key(0), key(1) & ~key(2), (key(3) | key(4)) & key(5),
             key(6) | key(7)]
    r_ref, c_ref = batch.execute_many(idx, preds, num_records=n,
                                      backend="ref")
    r_pal, c_pal = batch.execute_many(idx, preds, num_records=n,
                                      backend="pallas")
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_pal))
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c_pal))


def test_execute_many_buckets_amortize_traces():
    """A 200-query mix must land in a handful of canonical-shape buckets
    (the whole point: traces stay O(shapes), not O(queries))."""
    n, m = 64, 32
    records, keys = _random_index(n, m)
    idx = backends.get_backend("ref").create_index(records, keys)
    rng = np.random.default_rng(5)

    def k():
        return int(rng.integers(0, m))

    preds = []
    for i in range(200):
        preds.append([key(k()),
                      key(k()) & key(k()),
                      key(k()) & key(k()) & ~key(k()),
                      (key(k()) | key(k())) & key(k()),
                      key(k()) | key(k())][i % 5])
    before = batch.batched_executor_cache_info()
    rows, counts = batch.execute_many(idx, preds, num_records=n,
                                      backend="ref")
    after = batch.batched_executor_cache_info()
    assert after.currsize - before.currsize <= 5
    # and re-serving the same mix compiles nothing new
    batch.execute_many(idx, preds, num_records=n, backend="ref")
    again = batch.batched_executor_cache_info()
    assert again.currsize == after.currsize
    assert again.hits > after.hits


def test_execute_many_validates_key_range():
    records, keys = _random_index(40, 4)
    idx = backends.get_backend("ref").create_index(records, keys)
    with pytest.raises(ValueError, match=r"\[99\] out of range"):
        batch.execute_many(idx, [key(0), key(99)], num_records=40,
                           backend="ref")
    with pytest.raises(ValueError, match="out of range"):
        batch.execute_many(idx, [plan(key(99))], num_records=40,
                           backend="ref")


def test_execute_many_empty_batch():
    records, keys = _random_index(40, 4)
    idx = backends.get_backend("ref").create_index(records, keys)
    rows, counts = batch.execute_many(idx, [], num_records=40, backend="ref")
    assert rows.shape == (0, policy.num_words(40))
    assert counts.shape == (0,)


def test_biccore_query_many_matches_query():
    records, keys = _random_index(30, 8)
    core = BICCore(BICConfig(num_keys=8, num_records=30, words_per_record=8,
                             backend="ref"))
    bi = core.create(records, keys)
    preds = [key(2) & key(4) & ~key(5), key(1) | key(6), key(0)]
    rows, counts = core.query_many(bi, preds)
    for i, p in enumerate(preds):
        r, c = core.query(bi, where=p)
        np.testing.assert_array_equal(np.asarray(rows[i]), np.asarray(r))
        assert int(counts[i]) == int(c)


def test_serve_bitmap_query_step():
    from repro.serve.step import make_bitmap_query_step
    records, keys = _random_index(30, 8)
    core = BICCore(BICConfig(backend="ref"))
    bi = core.create(records, keys)
    step = make_bitmap_query_step(bi, backend="ref")
    rows, counts = step([key(1), key(2) & ~key(3)])
    for i, p in enumerate([key(1), key(2) & ~key(3)]):
        r, c = execute(bi.packed, p, num_records=bi.num_records,
                       backend="ref")
        np.testing.assert_array_equal(np.asarray(rows[i]), np.asarray(r))
        assert int(counts[i]) == int(c)


# --------------------------------------------------------- streaming append
@pytest.mark.parametrize("blocks", [
    [16, 16], [7, 32, 19, 1, 64], [31, 1, 33], [5],
    [3, 130],                 # block much larger than the existing index,
                              # crossing several 32-bit word boundaries
    [33, 95, 66],             # repeated non-aligned multi-word appends
])
def test_incremental_append_matches_rebuild(blocks):
    """Acceptance: appending block-by-block == indexing everything at once,
    including non-32-aligned intermediate record counts."""
    m, w = 21, 6
    keys = jnp.asarray(RNG.integers(0, 32, (m,), dtype=np.int32))
    si = StreamingIndexer(keys, backend="ref")
    all_blocks = []
    for b in blocks:
        blk = jnp.asarray(RNG.integers(0, 32, (b, w), dtype=np.int32))
        all_blocks.append(blk)
        si.append(blk)
        # the live index is consistent after EVERY append, not just the last
        n_so_far = sum(x.shape[0] for x in all_blocks)
        rebuilt = backends.get_backend("ref").create_index(
            jnp.concatenate(all_blocks, axis=0), keys)
        np.testing.assert_array_equal(np.asarray(si.index.packed),
                                      np.asarray(rebuilt))
        assert si.num_records == n_so_far


def test_append_empty_block_is_noop():
    """Satellite: a 0-record block must not dispatch create_index (the
    backends cannot index zero rows) and must leave the index untouched."""
    m, w = 9, 4
    keys = jnp.asarray(RNG.integers(0, 32, (m,), dtype=np.int32))
    si = StreamingIndexer(keys, backend="ref")
    empty = jnp.zeros((0, w), jnp.int32)
    si.append(empty)                         # empty append on empty index
    assert si.num_records == 0
    blk = jnp.asarray(RNG.integers(0, 32, (21, w), dtype=np.int32))
    si.append(blk)
    before = np.asarray(si.index.packed).copy()
    si.append(empty)
    assert si.num_records == 21
    np.testing.assert_array_equal(np.asarray(si.index.packed), before)
    # append_many with zero blocks / zero-record blocks is equally inert
    si.append_many(jnp.zeros((0, 8, w), jnp.int32))
    si.append_many(jnp.zeros((3, 0, w), jnp.int32))
    assert si.num_records == 21


def test_append_many_matches_sequential_and_rebuild():
    """Batched appends (one vmapped build + one scanned splice fold) are
    bit-identical to block-by-block appends and to a rebuild, including on
    top of a non-aligned prefix."""
    m, w = 21, 6
    keys = jnp.asarray(RNG.integers(0, 32, (m,), dtype=np.int32))
    prefix = jnp.asarray(RNG.integers(0, 32, (5, w), dtype=np.int32))
    blocks = jnp.asarray(RNG.integers(0, 32, (6, 7, w), dtype=np.int32))
    si_many = StreamingIndexer(keys, backend="ref")
    si_many.append(prefix)
    si_many.append_many(blocks)
    si_seq = StreamingIndexer(keys, backend="ref")
    si_seq.append(prefix)
    for b in blocks:
        si_seq.append(b)
    rebuilt = backends.get_backend("ref").create_index(
        jnp.concatenate([prefix, blocks.reshape(-1, w)], axis=0), keys)
    np.testing.assert_array_equal(np.asarray(si_many.index.packed),
                                  np.asarray(rebuilt))
    np.testing.assert_array_equal(np.asarray(si_seq.index.packed),
                                  np.asarray(rebuilt))
    assert si_many.num_records == si_seq.num_records == 47


def test_streaming_splice_not_retraced_per_block():
    """Acceptance: steady-state appends of one block size reuse a single
    compiled splice — the trace count must not grow with the block count."""
    m, w = 8, 4
    keys = jnp.asarray(RNG.integers(0, 32, (m,), dtype=np.int32))
    si = StreamingIndexer(keys, backend="ref", capacity_words=64)
    blk = jnp.asarray(RNG.integers(0, 32, (48, w), dtype=np.int32))
    si.append(blk)                           # first append traces once
    before = runtime.splice_cache_size()
    for _ in range(6):                       # non-aligned: offset cycles
        si.append(jnp.asarray(RNG.integers(0, 32, (48, w), dtype=np.int32)))
    assert runtime.splice_cache_size() == before


def test_fold_block_indexes_matches_rebuild():
    m, w = 13, 5
    keys = jnp.asarray(RNG.integers(0, 32, (m,), dtype=np.int32))
    rec = jnp.asarray(RNG.integers(0, 32, (4, 7, w), dtype=np.int32))
    be = backends.get_backend("ref")
    blocks = jnp.stack([be.create_index(r, keys) for r in rec])
    folded = fold_block_indexes(blocks, 7)
    rebuilt = be.create_index(rec.reshape(-1, w), keys)
    np.testing.assert_array_equal(np.asarray(folded.packed),
                                  np.asarray(rebuilt))
    assert folded.num_records == 28


def test_append_packed_is_pure_splice():
    m = 4
    a = jnp.asarray(RNG.integers(0, 2 ** 32, (m, 2), dtype=np.uint32))
    n_a = 45                                    # unaligned tail
    a = a & jnp.asarray(ref.pack_bits(
        (jnp.arange(64) < n_a).astype(jnp.uint32)).reshape(1, 2))
    b_bits = RNG.integers(0, 2, (m, 23)).astype(np.uint32)
    b = ref.pack_bits(jnp.asarray(np.pad(b_bits, ((0, 0), (0, 9)))))
    out = append_packed(a, n_a, b, 23)
    dense_a = np.asarray(ref.unpack_bits(a, n_a))
    dense_out = np.asarray(ref.unpack_bits(out, n_a + 23))
    np.testing.assert_array_equal(dense_out[:, :n_a], dense_a)
    np.testing.assert_array_equal(dense_out[:, n_a:], b_bits)


# --------------------------------------------------------- multicore runtime
def _one_device_mesh():
    import jax
    return jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def test_multicore_runtime_fuses_energy_and_execution():
    mesh = _one_device_mesh()
    rt = MulticoreRuntime(mesh, backend="ref")
    keys = jnp.asarray(RNG.integers(0, 256, (8,), dtype=np.int32))
    ticks = []
    for wl in (4, 0, 2):
        ticks.append(None if wl == 0 else jnp.asarray(
            RNG.integers(0, 256, (wl, 16, 32), dtype=np.int32)))
    outs, report = rt.index_stream(ticks, keys, tick_seconds=0.01)
    assert len(outs) == 2                       # idle tick produced no work
    assert outs[0].shape == (4, 8, 1)
    assert report.batches == 6
    assert report.active_joules > 0
    assert report.standby_joules > 0            # the idle tick was accounted
    # the indexes it produced match the single-core engine build
    core = BICCore(BICConfig(backend="ref"))
    for z in range(4):
        want = core.create(ticks[0][z], keys).packed
        np.testing.assert_array_equal(np.asarray(outs[0][z]),
                                      np.asarray(want))


def test_run_tick_serves_query_batch_against_tick_index():
    """run_tick(queries=...) folds the per-core block indexes into one tick
    index and serves the whole query batch through engine.batch —
    bit-identical to querying a from-scratch index of the tick's records."""
    mesh = _one_device_mesh()
    rt = MulticoreRuntime(mesh, backend="ref")
    keys = jnp.asarray(RNG.integers(0, 256, (8,), dtype=np.int32))
    records = jnp.asarray(RNG.integers(0, 256, (3, 16, 32), dtype=np.int32))
    queries = [key(0), key(1) & ~key(2), (key(3) | key(4)) & key(5)]
    res = rt.run_tick(records, keys, 0.01, queries=queries)
    assert res.indexes is not None
    assert res.query_rows.shape == (3, policy.num_words(48))
    tick_idx = backends.get_backend("ref").create_index(
        records.reshape(-1, 32), keys)
    for i, q in enumerate(queries):
        r, c = execute(tick_idx, q, num_records=48, backend="ref")
        np.testing.assert_array_equal(np.asarray(res.query_rows[i]),
                                      np.asarray(r))
        assert int(res.query_counts[i]) == int(c)
    # idle ticks and query-less ticks keep the old contract
    idle = rt.run_tick(None, keys, 0.01, queries=queries)
    assert idle.query_rows is None
    plain = rt.run_tick(records, keys, 0.01)
    assert plain.query_rows is None and plain.indexes is not None


_NON_DIVISIBLE_SCRIPT = """
import numpy as np, jax, jax.numpy as jnp
from repro.engine.runtime import multicore_create_index
from repro.core.bic import BICCore, BICConfig
assert len(jax.devices()) == 4, jax.devices()
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(3)
keys = jnp.asarray(rng.integers(0, 256, (8,), dtype=np.int32))
rec = jnp.asarray(rng.integers(0, 256, (6, 16, 32), dtype=np.int32))
out = multicore_create_index(rec, keys, mesh, backend="ref")   # 6 % 4 != 0
assert out.shape == (6, 8, 1), out.shape
core = BICCore(BICConfig(backend="ref"))
for z in range(6):
    want = core.create(rec[z], keys).packed
    np.testing.assert_array_equal(np.asarray(out[z]), np.asarray(want))
print("OK")
"""


def test_multicore_handles_non_divisible_batch_counts():
    """Workload sizes that don't divide the mesh axis pad for dispatch and
    slice back.  The pad branch only exists for >1 device, so this runs in
    a subprocess with a forced 4-device CPU platform (same trick as
    launch/dryrun.py)."""
    import os
    import subprocess
    import sys as _sys
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([_sys.executable, "-c", _NON_DIVISIBLE_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_multicore_create_index_backend_dispatch():
    mesh = _one_device_mesh()
    rec = jnp.asarray(RNG.integers(0, 256, (2, 16, 32), dtype=np.int32))
    keys = jnp.asarray(RNG.integers(0, 256, (8,), dtype=np.int32))
    a = multicore_create_index(rec, keys, mesh, backend="ref")
    b = multicore_create_index(rec, keys, mesh, backend="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
