"""The pipelined ``BitmapDB.append_encoded``: per-key counts kept on the
device and read only by ``stats``, at most ``_INFLIGHT_BLOCKS`` blocks
left unwaited, the int32 accumulator folded before it could overflow,
host blocks uploaded at the session's narrow width when their words fit,
and durability and visibility as before."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.db import BitmapDB
from repro.db import session as session_mod
from repro.engine import runtime
from repro.engine.planner import key
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

M = 16
#: unaligned sizes, so splices shift and carry across words
BLOCKS = (37, 64, 5, 96, 33, 1, 70)


def _records(rng, n):
    # words outside [0, M) match no key
    return rng.integers(-2, M + 3, (n, 3), dtype=np.int32)


def _exact(db):
    st = db.stats
    assert st.num_records == db.num_records
    assert st.counts == tuple(int(c) for c in
                              session_mod._popcounts(db.index.packed))


@pytest.mark.parametrize("mode", ["memory", "path", "reopened"])
def test_stats_equal_the_live_index_popcounts(tmp_path, mode):
    rng = np.random.default_rng(21)
    path = os.path.join(str(tmp_path), "db") if mode != "memory" else None
    db = BitmapDB(num_keys=M, path=path, backend="ref", spill_records=100)
    for i, n in enumerate(BLOCKS):
        db.append_encoded(_records(rng, n))
        if i % 3 == 1:
            _exact(db)
    if mode == "reopened":
        db = BitmapDB.open(path, num_keys=M, backend="ref",
                           spill_records=100)
        assert db.num_records == sum(BLOCKS)
        _exact(db)
        for n in BLOCKS[:4]:
            db.append_encoded(_records(rng, n))
    _exact(db)


def test_queries_between_appends_answer_as_numpy():
    rng = np.random.default_rng(22)
    db = BitmapDB(num_keys=M, backend="ref")
    preds = [key(1) & ~key(2), key(3) | key(4), ~key(0),
             (key(5) & key(6)) | ~key(7)]

    def want(p, recs):
        hit = {i: (recs == i).any(axis=1) for i in range(8)}
        return [hit[1] & ~hit[2], hit[3] | hit[4], ~hit[0],
                (hit[5] & hit[6]) | ~hit[7]][preds.index(p)]

    seen, pending = [], []
    for n in BLOCKS:
        seen.append(_records(rng, n))
        db.append_encoded(seen[-1])
        recs = np.concatenate(seen)
        # planned now (reading the stats), materialized after later appends
        pending.append((recs, db.query_many(preds)))
    for recs, batch in pending:
        for p, r in zip(preds, batch):
            np.testing.assert_array_equal(r.ids,
                                          np.flatnonzero(want(p, recs)))


def test_appends_read_nothing_back_until_stats_asks():
    counter = obs_metrics.GLOBAL.counter("db_ingest_readbacks_total")
    rng = np.random.default_rng(23)
    db = BitmapDB(num_keys=M, backend="ref")
    before = counter.value
    for n in BLOCKS:
        db.append_encoded(_records(rng, n))
    assert counter.value == before
    _exact(db)
    assert counter.value == before + 1          # one (M,) read
    _exact(db)
    assert counter.value == before + 1          # nothing new: cached


def test_at_most_two_blocks_are_left_unwaited():
    rng = np.random.default_rng(24)
    db = BitmapDB(num_keys=M, backend="ref")
    depth = session_mod._INFLIGHT_BLOCKS
    assert depth == 2
    accs = []
    for n in BLOCKS:
        db.append_encoded(_records(rng, n))
        accs.append(db._acc)
        assert list(db._inflight) == accs[-depth:]
        assert all(a.is_ready() for a in accs[:-depth])


def test_count_accumulator_folds_before_overflow(monkeypatch):
    monkeypatch.setattr(session_mod, "_COUNT_FOLD_RECORDS", 100)
    counter = obs_metrics.GLOBAL.counter("db_ingest_readbacks_total")
    rng = np.random.default_rng(25)
    db = BitmapDB(num_keys=M, backend="ref")
    before = counter.value
    for n in BLOCKS:
        db.append_encoded(_records(rng, n))
        assert db._acc_records <= 100
    # folds happened on the append path: 37+64 > 100, 5+96 > 100, ...
    assert counter.value > before
    _exact(db)


def test_durable_append_is_in_the_wal_without_a_device_read(tmp_path,
                                                            monkeypatch):
    """A host block is logged as the caller passed it (no device round
    trip); a device block falls back to reading the uploaded array."""
    rng = np.random.default_rng(26)
    db = BitmapDB(num_keys=M, path=str(tmp_path), backend="ref",
                  spill_records=None)
    host = _records(rng, 40)
    dev = jnp.asarray(_records(rng, 24))

    def no_read(*_):
        raise AssertionError("host records read back from the device")

    with monkeypatch.context() as mp:
        mp.setattr(runtime.jax, "device_get", no_read)
        db.append_encoded(host)
    db.append_encoded(dev)
    wal = db.store.replay_wal()
    assert [start for start, _, _ in wal] == [0, 40]
    np.testing.assert_array_equal(wal[0][1], host)
    np.testing.assert_array_equal(wal[1][1], np.asarray(dev))
    assert wal[0][1].dtype == wal[1][1].dtype == np.int32


def _numpy_index(recs, m):
    """Key-major packed rows (m, ceil(n/32)) uint32 of ``recs``."""
    hits = np.stack([(recs == k).any(axis=1) for k in range(m)])
    hits = np.pad(hits, ((0, 0), (0, -len(recs) % 32)))
    return np.packbits(hits, axis=1, bitorder="little").view("<u4")


def _upload_bytes(tracer):
    return [sp.attrs["bytes"] for sp in tracer.spans()
            if sp.name == "ingest.upload"]


#: (low, high) of a block's words, and a word planted in block 1 that
#: sends it as int32: words in [M, 256) still cross as uint8 and match
#: no key, a negative word or one of 256 or more does not fit uint8
NARROW_CASES = {"keys": (0, M, None), "above_keys": (0, 256, None),
                "negative": (0, M, -3), "sentinel": (0, M, -1),
                "wide": (0, M, 256), "wider": (0, M, 70_000)}


@pytest.mark.parametrize("case", sorted(NARROW_CASES))
@pytest.mark.parametrize("backend", ["ref", "bulk", "pallas"])
def test_narrowed_and_int32_uploads_build_the_same_index(monkeypatch,
                                                         backend, case):
    """A host block whose words all fit uint8 crosses as uint8 (counted,
    1 byte a word); a block with a word outside [0, 256) crosses as
    int32, exactly as a session that never narrows; both build the index
    NumPy builds."""
    low, high, planted = NARROW_CASES[case]
    counter = obs_metrics.GLOBAL.counter("db_ingest_narrow_uploads_total")
    rng = np.random.default_rng(27)
    blocks = [rng.integers(low, high, (n, 3), dtype=np.int32)
              for n in BLOCKS[:3]]
    if planted is not None:
        blocks[1][7, 2] = planted
    narrow = BitmapDB(num_keys=M, backend=backend)
    assert narrow._narrow == np.uint8
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        before = counter.value
        for b in blocks:
            narrow.append_encoded(b)
        sent = counter.value - before
    finally:
        obs_trace.uninstall(tracer)
    with monkeypatch.context() as mp:
        mp.setattr(session_mod, "_NARROW_DTYPES", ())
        wide = BitmapDB(num_keys=M, backend=backend)
    assert wide._narrow is None
    before = counter.value
    for b in blocks:
        wide.append_encoded(b)
    assert counter.value == before
    fits = [planted is None or i != 1 for i in range(len(blocks))]
    assert sent == sum(fits)
    assert _upload_bytes(tracer) == [b.size * (1 if f else 4)
                                     for b, f in zip(blocks, fits)]
    got = np.asarray(narrow.index.packed)
    np.testing.assert_array_equal(got, np.asarray(wide.index.packed))
    np.testing.assert_array_equal(got, _numpy_index(np.concatenate(blocks),
                                                    M))
    assert narrow.stats.counts == wide.stats.counts


@pytest.mark.parametrize("num_keys, dtype", [
    (1, np.uint8), (256, np.uint8), (257, np.uint16), (1_795, np.uint16),
    (65_536, np.uint16), (65_537, None)])
def test_a_session_narrows_to_the_least_width_of_its_keys(num_keys, dtype):
    assert session_mod._narrow_dtype(num_keys) == dtype


def test_a_1795_key_session_uploads_uint16():
    """SSB's 1,795 key rows: words up to 65,535 cross as uint16 (2 bytes a
    word), and those at or past 1,795 match no key."""
    m = 1_795
    counter = obs_metrics.GLOBAL.counter("db_ingest_narrow_uploads_total")
    rng = np.random.default_rng(28)
    recs = rng.integers(0, m, (70, 4), dtype=np.int32)
    recs[::5, 0] = rng.integers(m, 1 << 16, 14)
    db = BitmapDB(num_keys=m, backend="ref")
    assert db._narrow == np.uint16
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        before = counter.value
        db.append_encoded(recs)
        assert counter.value == before + 1
    finally:
        obs_trace.uninstall(tracer)
    assert _upload_bytes(tracer) == [recs.size * 2]
    np.testing.assert_array_equal(np.asarray(db.index.packed),
                                  _numpy_index(recs, m))


def test_a_narrowed_durable_append_logs_int32_and_recovers(tmp_path):
    """The WAL holds the caller's int32 records, not the uint8 upload, and
    recovery rebuilds the same index from it."""
    counter = obs_metrics.GLOBAL.counter("db_ingest_narrow_uploads_total")
    rng = np.random.default_rng(29)
    blocks = [rng.integers(0, 256, (n, 3), dtype=np.int32)
              for n in BLOCKS[:4]]
    db = BitmapDB(num_keys=M, path=str(tmp_path), backend="ref",
                  spill_records=None)
    before = counter.value
    for b in blocks:
        db.append_encoded(b)
    assert counter.value == before + len(blocks)
    wal = db.store.replay_wal()
    assert [start for start, _, _ in wal] == list(
        np.cumsum([0, *BLOCKS[:3]]))
    for (_, logged, _), b in zip(wal, blocks):
        assert logged.dtype == np.int32
        np.testing.assert_array_equal(logged, b)
    want = np.asarray(db.index.packed)
    del db
    back = BitmapDB.open(str(tmp_path), num_keys=M, backend="ref",
                         spill_records=None)
    np.testing.assert_array_equal(np.asarray(back.index.packed), want)
    np.testing.assert_array_equal(want, _numpy_index(np.concatenate(blocks),
                                                     M))
