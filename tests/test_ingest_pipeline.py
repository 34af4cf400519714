"""The pipelined ``BitmapDB.append_encoded``: per-key counts kept on the
device and read only by ``stats``, at most ``_INFLIGHT_BLOCKS`` blocks
left unwaited, the int32 accumulator folded before it could overflow,
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
