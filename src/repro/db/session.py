"""`BitmapDB` — the one schema-aware session object over engine + store.

The paper's silicon hides packing, carry-splicing, and power-mode detail
behind a simple ingest/query port; this class is that port for the whole
reproduction stack.  One object owns:

  * **ingest** — :meth:`ingest` / :meth:`append` encode structured rows
    through the :class:`repro.db.Schema` and stream them into a
    :class:`repro.engine.runtime.StreamingIndexer` (jitted shift/carry
    splice, no rebuild); :meth:`append_encoded` takes pre-encoded key-word
    records directly (the data-pipeline path).
  * **durability** — opened with ``path=``, every append is WAL-logged
    before the in-memory splice and the tail auto-spills as immutable
    segments past ``spill_records`` (:mod:`repro.store`); :meth:`snapshot`
    force-spills, and :func:`BitmapDB.open` recovers a crashed session
    bit-identically from manifest + WAL (the schema persists as
    ``SCHEMA.json`` next to the segments).
  * **query** — :meth:`query` / :meth:`query_many` accept DSL expressions
    (``col("city") == "SF"``), raw engine predicates (``key(3) & ~key(5)``),
    or pre-built plans; lowering and planning cache per expression, plans
    order their DNF clauses by the session's live per-key selectivity
    stats (:class:`repro.engine.planner.KeyStats`), and execution runs
    through the engine's bucketed batch executors.  Results come back as
    lazy :class:`repro.db.Result` handles.
  * **serving** — :meth:`serve_step` wraps the bucketed batch executor as
    a raw ``(rows, counts)`` step function for serving loops
    (:mod:`repro.serve.step` routes through it).

Read-only sessions wrap an existing index: :meth:`BitmapDB.from_index`
accepts an in-memory :class:`repro.engine.policy.BitmapIndex` or a
segment-backed :class:`repro.store.StoredIndex` (served segment-parallel,
stacked into one vmapped dispatch when word counts are uniform).
"""
from __future__ import annotations

import collections
import os
import threading
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.db import expr as expr_mod
from repro.db.result import LazyBatch, Result, ResultBatch
from repro.db.schema import Schema
from repro.obs import metrics as obs_metrics
from repro.obs.trace import maybe_span
from repro.engine import (backends, batch as engine_batch, costmodel,
                          planner, policy)
from repro.engine.runtime import StreamingIndexer

SCHEMA_FILE = "SCHEMA.json"

_INGEST_READBACKS = obs_metrics.GLOBAL.counter(
    "db_ingest_readbacks_total",
    "blocking device-to-host reads of the per-key counts that "
    "append_encoded keeps on the device")
_INGEST_NARROW_UPLOADS = obs_metrics.GLOBAL.counter(
    "db_ingest_narrow_uploads_total",
    "host record blocks append_encoded sent to the device at the "
    "session's narrow width instead of int32")

#: unsigned widths a host block may cross to the device at, narrowest
#: first: a session takes the first that holds every key id it has
_NARROW_DTYPES = (np.dtype(np.uint8), np.dtype(np.uint16))

#: rows range-checked and then cast per step, so that the cast reads the
#: chunk from cache: 4,096 rows of 32 int32 words are 512 KiB
_NARROW_CHUNK_ROWS = 4096

#: blocks ``append_encoded`` leaves unfinished on the device.  Two let
#: block k+1's host-to-device copy run under block k's build, while the
#: host cannot queue more record blocks, nor more splice outputs (each
#: the size of the session's buffer), in HBM than that.
_INFLIGHT_BLOCKS = 2

#: the device count accumulator is int32: it is folded into the host's
#: int64 counts before the records it covers could pass int32's range
_COUNT_FOLD_RECORDS = 2**31 - 1


def include_exclude_pred(include: Sequence[int] = (),
                         exclude: Sequence[int] = ()) -> planner.Pred:
    """Deprecation shim for the legacy ``include=``/``exclude=`` call
    surface: AND of positive/negated key-row literals, byte-identical to
    what those callers always got.  Callers that passed NEITHER list get
    the original empty-query ValueError, no warning — they used nothing
    deprecated."""
    if include or exclude:
        warnings.warn(
            "include=/exclude= key lists are deprecated; use a repro.db "
            "expression (col(...) == value) or an engine predicate "
            "(key(i) & ~key(j))", DeprecationWarning, stacklevel=3)
    return planner.from_include_exclude(include, exclude)


def _narrow_dtype(num_keys: int) -> np.dtype | None:
    """The narrowest unsigned dtype that holds every key id below
    ``num_keys``, or None when none of ``_NARROW_DTYPES`` does."""
    return next((dt for dt in _NARROW_DTYPES
                 if num_keys <= 1 << (8 * dt.itemsize)), None)


def _narrowed(records: np.ndarray,
              dtype: np.dtype | None) -> np.ndarray | None:
    """int32 ``records`` cast to ``dtype`` when every word lies in
    [0, 2^bits) of it, else None (the block then crosses as int32, as it
    does in a session without a narrow dtype).

    One pass: each chunk's maximum over a uint32 view (a negative word
    reads as 2^31 or more) decides, and the cast reads the chunk again
    from cache."""
    if dtype is None:
        return None
    top = np.iinfo(dtype).max
    words = records.view(np.uint32)
    out = np.empty(records.shape, dtype)
    for s in range(0, records.shape[0], _NARROW_CHUNK_ROWS):
        chunk = words[s:s + _NARROW_CHUNK_ROWS]
        if chunk.max(initial=0) > top:
            return None
        out[s:s + _NARROW_CHUNK_ROWS] = chunk
    return out


def _popcounts(packed) -> np.ndarray:
    """Exact per-key set-bit counts of a packed (M, W) array."""
    arr = np.asarray(jax.device_get(packed))
    if arr.size == 0:
        return np.zeros((arr.shape[0],), np.int64)
    return np.bitwise_count(arr).sum(axis=1, dtype=np.int64)


@jax.jit
def _add_popcounts(acc: jax.Array, block: jax.Array) -> jax.Array:
    """``acc`` (M,) int32 plus the per-key set-bit counts of ``block``."""
    return acc + jnp.sum(jax.lax.population_count(block), axis=1,
                         dtype=jnp.int32)


class BitmapDB:
    """One bitmap-index database session (see module docstring)."""

    def __init__(self, schema: Schema | None = None, *,
                 num_keys: int | None = None, path: str | None = None,
                 backend: str = "auto", spill_records: int | None = 4096,
                 capacity_words: int = 16, device=None,
                 _restore: bool = False):
        if schema is None and num_keys is None:
            raise ValueError("BitmapDB needs a Schema (or num_keys= for a "
                             "raw key-addressed session)")
        if schema is not None and num_keys is not None \
                and num_keys != schema.num_keys:
            raise ValueError(f"num_keys={num_keys} contradicts the schema "
                             f"({schema.num_keys} keys)")
        self.schema = schema
        # "auto" stays UNRESOLVED: the query path hands it to the engine,
        # where the measured cost model picks per wave.  Index creation is
        # one fixed bulk pass, so that side pins a concrete backend now.
        self.backend = ("auto" if backend == "auto"
                        else backends.resolve_backend(backend))
        self._create_backend = backends.resolve_backend(backend)
        self.path = path
        m = schema.num_keys if schema is not None else int(num_keys)
        self._keys = jnp.arange(m, dtype=jnp.int32)
        # host record blocks cross to the device at this width when their
        # words allow it (see append_encoded)
        self._narrow = _narrow_dtype(m)
        if device is not None:
            # pin the session to one device: index creation, the capacity
            # buffer and every query dispatch follow the committed keys
            # (one process serving a shard per chip)
            self._keys = jax.device_put(self._keys, device)
        self._index = None                     # read-only sessions only
        # exact per-key counts: host int64 ``_counts`` plus the device
        # int32 ``_acc`` of the blocks appended since the last fold;
        # ``_counted`` records are covered by the two together
        self._counts = np.zeros((m,), np.int64)
        self._acc = jnp.zeros_like(self._keys)
        self._acc_records = 0
        self._counted = 0
        self._count_mu = threading.Lock()
        self._inflight: collections.deque = collections.deque()
        self._plans: dict = {}
        self._plans_by_id: dict = {}       # id(expr) fast path (see _plan_for)
        # typed counters in a per-session registry; cache_stats() is a
        # view over these (services attach the registry as their "db"
        # subtree for one exportable metric tree)
        self.registry = obs_metrics.Registry()
        self._cache_counters = {
            k: self.registry.counter(f"plan_cache_{k}_total")
            for k in ("id_hits", "value_hits", "misses",
                      "id_evictions", "value_evictions")}
        self._stats_cache: tuple[int, planner.KeyStats] | None = None
        self._view_cache = None            # (buf, n, BitmapIndex) snapshot
        if path is None:
            self._si = StreamingIndexer(self._keys,
                                        backend=self._create_backend,
                                        capacity_words=capacity_words)
            return
        from repro.store import SegmentStore
        store = SegmentStore(path)
        self._persist_schema(path)
        if _restore:
            self._si = StreamingIndexer.restore(
                store, self._keys, backend=self._create_backend,
                capacity_words=capacity_words, flush_records=spill_records)
            self._counts = _popcounts(self._si.index.packed)
            self._counted = self._si.num_records
            return
        self._si = StreamingIndexer(self._keys,
                                    backend=self._create_backend,
                                    capacity_words=capacity_words)
        try:
            self._si.attach_store(store, flush_records=spill_records)
        except ValueError as e:
            raise ValueError(
                f"{path} already holds a durable index; resume it with "
                f"repro.db.open({path!r}) instead of BitmapDB(path=...)"
            ) from e

    # ------------------------------------------------------------ open/wrap
    @classmethod
    def open(cls, path: str, schema: Schema | None = None, *,
             num_keys: int | None = None, backend: str = "auto",
             spill_records: int | None = 4096,
             capacity_words: int = 16) -> "BitmapDB":
        """Recover a durable session from ``path``: committed segments +
        surviving WAL blocks replay into a live index bit-identical to the
        pre-crash one, with per-key stats recounted exactly from the
        recovered packed rows.  The schema is loaded from the persisted
        ``SCHEMA.json`` when not given (and verified against it when it
        is); ``num_keys=`` opens a raw key-addressed store that never had
        one."""
        sf = os.path.join(path, SCHEMA_FILE)
        if schema is None and os.path.exists(sf):
            with open(sf) as f:           # noqa: PLW1514 (ascii json)
                schema = Schema.from_json(f.read())
        if schema is None and num_keys is None:
            raise FileNotFoundError(
                f"{sf} not found — pass schema= or num_keys= to open a "
                "store created without a persisted schema")
        return cls(schema, num_keys=None if schema is not None else num_keys,
                   path=path, backend=backend, spill_records=spill_records,
                   capacity_words=capacity_words, _restore=True)

    @classmethod
    def from_index(cls, index, schema: Schema | None = None, *,
                   backend: str = "auto") -> "BitmapDB":
        """Wrap an existing index as a READ-ONLY query session: an
        in-memory :class:`repro.engine.policy.BitmapIndex` or a
        segment-backed :class:`repro.store.StoredIndex` (served
        segment-parallel).  Appends raise; stats come from exact popcounts
        on first use."""
        m = int(index.num_keys)
        if schema is not None and schema.num_keys != m:
            raise ValueError(f"index has {m} key rows but the schema "
                             f"defines {schema.num_keys}")
        db = cls(schema, num_keys=m if schema is None else None,
                 backend=backend)
        db._si = None
        db._index = index
        db._counts = None                  # lazily popcounted
        return db

    # ----------------------------------------------------------- properties
    @property
    def num_keys(self) -> int:
        return int(self._keys.shape[0])

    @property
    def num_records(self) -> int:
        if self._si is not None:
            return self._si.num_records
        return int(self._index.num_records)

    @property
    def index(self) -> policy.BitmapIndex:
        """The live contiguous index (read-only StoredIndex sessions stay
        segment-parallel — materialize explicitly if you must)."""
        if self._si is not None:
            return self._si.index
        if isinstance(self._index, policy.BitmapIndex):
            return self._index
        raise TypeError(
            "this session serves a segment-backed StoredIndex; use "
            "query()/query_many(), or index.to_bitmap_index() to "
            "materialize")

    @property
    def store(self) -> "SegmentStore":
        return self._si.store if self._si is not None else None

    @property
    def indexer(self) -> "StreamingIndexer":
        """The live :class:`repro.engine.runtime.StreamingIndexer` (None
        for read-only ``from_index`` sessions) — the hook point service
        maintenance uses to move spills off the append path."""
        return self._si

    @property
    def stats(self) -> planner.KeyStats:
        """Live per-key set-bit counts (exact) as planner cardinality
        estimates.  A live session reads its device counts (one (M,)
        int32 read) only when records were counted since the last call."""
        if self._counts is None:           # read-only: popcount on demand
            idx = self._index
            if hasattr(idx, "parts"):      # StoredIndex
                c = np.zeros((self.num_keys,), np.int64)
                for part, _ in idx.parts:
                    c += _popcounts(part)
                self._counts = c
            else:
                self._counts = _popcounts(idx.packed)
            self._counted = self.num_records
        with self._count_mu:
            n = self._counted
            if self._stats_cache is None or self._stats_cache[0] != n:
                self._fold_counts()
                self._stats_cache = (n, planner.KeyStats(
                    tuple(int(c) for c in self._counts), n))
            return self._stats_cache[1]

    def _fold_counts(self) -> None:
        """Add the device accumulator into the host counts and restart it
        (caller holds ``_count_mu``)."""
        if self._acc_records:
            self._counts += np.asarray(jax.device_get(self._acc))
            _INGEST_READBACKS.inc()
            self._acc = jnp.zeros_like(self._acc)
            self._acc_records = 0

    def _wait_inflight(self) -> None:
        """With ``_INFLIGHT_BLOCKS`` blocks unwaited, wait for the oldest:
        its count was dispatched after its splice, and the device runs
        programs in order."""
        with self._count_mu:
            if len(self._inflight) < _INFLIGHT_BLOCKS:
                return
            oldest = self._inflight.popleft()
        oldest.block_until_ready()

    def _count_block(self, block: jax.Array, n: int) -> None:
        """Add ``block``'s per-key counts on the device."""
        with self._count_mu:
            if self._acc_records + n > _COUNT_FOLD_RECORDS:
                self._fold_counts()
            self._acc = _add_popcounts(self._acc, block)
            self._acc_records += n
            self._counted += n
            self._inflight.append(self._acc)

    # --------------------------------------------------------------- ingest
    def ingest(self, rows) -> int:
        """Bulk-load structured rows (see :meth:`repro.db.Schema.encode`
        for accepted shapes); returns the new total record count."""
        return self.append(rows)

    def append(self, rows) -> int:
        """Stream structured rows into the live index (auto-spilling past
        the ``spill_records`` threshold when opened with ``path=``)."""
        if self.schema is None:
            raise ValueError("this session has no Schema; use "
                             "append_encoded with raw key-word records")
        return self.append_encoded(self.schema.encode(rows))

    def append_encoded(self, records) -> int:
        """Stream pre-encoded key-word records (N, W): each int word is a
        global key id (words outside [0, num_keys) match no key).

        A host block whose words all lie in [0, 2^bits) of the session's
        narrow dtype (uint8 up to 256 keys, uint16 up to 65,536) crosses
        to the device at that width and is widened to int32 there; any
        other block, and a device array, goes as int32.  The WAL logs the
        int32 records either way.

        Returns once the block's copy, index creation and splice are
        dispatched, leaving at most ``_INFLIGHT_BLOCKS`` blocks unfinished
        on the device: the next block's copy runs under this block's
        build.  A view or query taken after the return includes the block
        (the splice is ordered on the buffer), and a durable block is in
        the WAL.  Per-key counts stay on the device until :attr:`stats`
        reads them.

        Traced, a block is one ``ingest.append`` span whose children run
        in order: ``ingest.upload`` (range check, narrowing and
        host-to-device copy; attribute ``bytes``, the bytes sent),
        ``ingest.create`` (index creation dispatched), ``ingest.wait``
        (the device finishes the block ``_INFLIGHT_BLOCKS`` back, before
        this splice allocates its output), ``ingest.splice`` (WAL, grow,
        splice, spill) and ``ingest.count`` (the block's per-key counts
        added on the device)."""
        if self._si is None:
            raise RuntimeError("read-only session (from_index) — open a "
                               "BitmapDB with a schema/path to ingest")
        shape = np.shape(records)
        if len(shape) != 2:
            raise ValueError(f"records must be (N, W), got {shape}")
        if not shape[0]:
            return self.num_records
        if not isinstance(records, jax.Array):
            # host records: the WAL logs this array, no device round trip
            records = np.asarray(records, np.int32)
        with maybe_span("ingest.append", records=shape[0],
                        backend=self._create_backend):
            with maybe_span("ingest.upload") as sp:
                narrow = (None if isinstance(records, jax.Array)
                          else _narrowed(records, self._narrow))
                if narrow is None:
                    dev = jnp.asarray(records, jnp.int32)
                else:
                    dev = jnp.asarray(narrow)
                    _INGEST_NARROW_UPLOADS.inc()
                if sp is not None:
                    sp.attrs["bytes"] = dev.nbytes
                    # the copy returns before its DMA ends: traced, the
                    # span waits for it, so the copy is not timed as wait
                    dev.block_until_ready()
            with maybe_span("ingest.create"):
                block = backends.get_backend(
                    self._create_backend).create_index(dev, self._keys)
            with maybe_span("ingest.wait"):
                self._wait_inflight()
            with maybe_span("ingest.splice"):
                self._si.append_indexed(
                    records if isinstance(records, np.ndarray) else dev,
                    block)
            with maybe_span("ingest.count"):
                self._count_block(block, shape[0])
        return self.num_records

    # ----------------------------------------------------------- durability
    def snapshot(self) -> None:
        """Force-spill the in-memory tail as an immutable segment (atomic
        manifest commit); a no-op when nothing new arrived."""
        if self._si is None or self._si.store is None:
            raise RuntimeError("no store attached — open the BitmapDB "
                               "with path= to make it durable")
        self._si.spill()

    def _persist_schema(self, path: str) -> None:
        if self.schema is None:
            return
        from repro.store import format as fmt
        os.makedirs(path, exist_ok=True)
        sf = os.path.join(path, SCHEMA_FILE)
        if os.path.exists(sf):
            with open(sf) as f:
                stored = Schema.from_json(f.read())
            if stored != self.schema:
                raise ValueError(
                    f"{path} was created with a different schema "
                    f"({stored!r}); one store persists ONE schema")
        else:
            fmt.write_bytes_atomic(sf, self.schema.to_json().encode())

    # ---------------------------------------------------------------- query
    #: cache entries above this are dropped wholesale — bounds memory for
    #: workloads that build every expression object fresh (id cache) or
    #: never repeat a value (value cache); both limits are deliberately
    #: the same so neither cache can outgrow the other.
    _ID_CACHE_LIMIT = 65536
    _VALUE_CACHE_LIMIT = 65536

    def _plan_for(self, q):
        # serving loops re-submit the same expression OBJECTS: an identity
        # hit skips even the value-hash of a nested tree.  Entries keep a
        # strong reference to the query, so a cached id can never be a
        # recycled object's — a hit IS the same object.
        c = self._cache_counters
        hit = self._plans_by_id.get(id(q))
        if hit is not None:
            c["id_hits"].inc()
            return hit[1]
        if isinstance(q, (planner.QueryPlan, planner.FactoredPlan,
                          planner.CompositePlan)):
            return q
        pl = self._plans.get(q)
        if pl is None:
            c["misses"].inc()
            pred = expr_mod.lower(q, self.schema)
            planner.check_key_range(planner.key_indices(pred),
                                    self.num_keys)
            # stats ordering is opportunistic: live sessions maintain
            # counts incrementally; a read-only wrapper only pays the
            # popcount if the caller already asked for .stats
            stats = self.stats if self._counts is not None else None
            pl = planner.plan(pred, stats=stats)
            if len(self._plans) >= self._VALUE_CACHE_LIMIT:
                c["value_evictions"].add(len(self._plans))
                self._plans.clear()
            self._plans[q] = pl
        else:
            c["value_hits"].inc()
        if len(self._plans_by_id) >= self._ID_CACHE_LIMIT:
            c["id_evictions"].add(len(self._plans_by_id))
            self._plans_by_id.clear()
        self._plans_by_id[id(q)] = (q, pl)
        return pl

    def cache_stats(self) -> dict:
        """Plan-cache health for service metrics: hit/miss/eviction
        counters plus the live sizes of the identity-keyed and
        value-keyed caches (both bounded at 64k entries, dropped
        wholesale at the limit)."""
        out = {k: c.value for k, c in self._cache_counters.items()}
        out["id_size"] = len(self._plans_by_id)
        out["value_size"] = len(self._plans)
        return out

    def replan(self) -> None:
        """Drop the per-expression plan cache so future queries re-order
        their clauses against the CURRENT selectivity stats (ordering is a
        perf detail — cached plans stay correct forever)."""
        self._plans.clear()
        self._plans_by_id.clear()
        self._stats_cache = None

    def _execute(self, plans: Sequence, view, pad_output: bool = False,
                 backend: str | None = None) -> tuple:
        # live sessions hand their exact per-key stats to the cost model
        # (read-only wrappers only once the caller has paid for .stats)
        stats = self.stats if self._counts is not None else None
        be = backend if backend is not None else self.backend
        if hasattr(view, "parts"):              # StoredIndex
            return engine_batch.execute_many_segments(
                view.parts, plans, backend=be, stats=stats)
        return engine_batch.execute_many(
            view.packed, plans, num_records=view.num_records,
            backend=be, pad_output=pad_output, stats=stats)

    def _view(self):
        """Immutable snapshot the lazy batch executes against — a query
        sees the db as of query() time even if materialized after later
        appends (packed buffers are functional jax arrays).  The packed
        slice out of the indexer's capacity buffer is cached per
        (buffer, record count): a steady-state serving loop re-queries
        without re-copying the index."""
        if self._si is None:
            return self._index
        buf, n = self._si.view()           # consistent under appends
        c = self._view_cache
        if c is not None and c[0] is buf and c[1] == n:
            return c[2]
        idx = policy.BitmapIndex(buf[:, :policy.num_words(n)], n)
        self._view_cache = (buf, n, idx)
        return idx

    def query(self, q) -> Result:
        """One expression / predicate / plan -> a lazy :class:`Result`."""
        return self.query_many([q])[0]

    def explain(self, q) -> dict:
        """How this session would run ``q`` — without running it.

        Returns a plain dict: the cached plan object (``plan``), its
        lowered pass ``program`` and canonical padded ``bucket_shape``
        (None for composite fallbacks / contradictions), the KeyStats
        selectivity estimate (``est_matches`` / ``est_selectivity``, None
        without stats), the ``backend`` a dispatch would land on right
        now, and — when the session runs ``auto`` — the full cost-model
        ``decision``: per-candidate time ``estimates``, the chosen
        factoring/stacking, and the model's input ``terms``.  Purely
        observational: no device work, no cache perturbation beyond plan
        lowering.
        """
        pl = self._plan_for(q)
        view = self._view()
        if hasattr(view, "parts"):              # StoredIndex
            segments = len(view.parts)
            num_words = max((p.shape[1] for p, _ in view.parts), default=0)
        else:
            segments = 1
            num_words = view.packed.shape[1]
        stats = self.stats if self._counts is not None else None
        out: dict = {
            "plan": pl,
            "program": None,
            "bucket_shape": None,
            "num_records": self.num_records,
            "num_words": num_words,
            "segments": segments,
            "est_matches": None,
            "est_selectivity": None,
        }
        if isinstance(pl, planner.CompositePlan):
            out["fallback"] = "composite"       # served via planner.execute
        else:
            prog, shape, _, _ = engine_batch._lowered(pl)
            out["program"] = prog
            out["bucket_shape"] = shape
            if shape is None:
                out["fallback"] = "contradiction"   # constant all-zeros
        em = costmodel.estimate_matches([pl], stats)
        if em is not None:
            out["est_matches"] = em
            out["est_selectivity"] = (em / self.num_records
                                      if self.num_records else 0.0)
        if self.backend == "auto":
            decision = costmodel.decide(
                [pl], num_words=num_words, num_segments=segments,
                num_keys=self.num_keys, stats=stats)
            out["backend"] = decision.backend
            out["decision"] = {
                "backend": decision.backend,
                "factor": decision.factor,
                "stack_uniform": decision.stack_uniform,
                "estimates": dict(decision.estimates),
                "terms": dict(decision.terms),
            }
        else:
            out["backend"] = self.backend
            out["decision"] = None
        return out

    def query_many(self, queries: Sequence, *, pad_output: bool = False,
                   backend: str | None = None) -> ResultBatch:
        """A batch of expressions in ONE lazily executed bucketed dispatch
        set; returns a :class:`ResultBatch` (sequence of lazy
        :class:`Result` handles, in input order).  ``pad_output=True``
        pads the materialized arrays' query axis to a power of two
        (handles still cover exactly the submitted queries) — the
        serving scheduler uses this so varying coalesced batch sizes
        reuse compiled shapes instead of retracing.  ``backend=``
        overrides the session backend for this one batch — the serving
        path's circuit breaker uses it to route a wave to its fallback
        backend without touching session state."""
        if not isinstance(queries, (list, tuple)):
            queries = list(queries)
        # inlined _plan_for fast path: submission of a steady-state
        # serving batch costs one dict probe per query
        byid = self._plans_by_id
        plan_for = self._plan_for
        plans = []
        append = plans.append
        fast_hits = 0
        for q in queries:
            hit = byid.get(id(q))
            if hit is not None:
                fast_hits += 1
                append(hit[1])
            else:
                append(plan_for(q))
        if fast_hits:
            self._cache_counters["id_hits"].add(fast_hits)
        view = self._view()
        batch_run = LazyBatch(
            lambda: self._execute(plans, view, pad_output, backend))
        return ResultBatch(batch_run, self.num_records, queries)

    def serve_step(self):
        """The bucketed batch executor as a serving-loop step function:
        ``step(queries) -> (rows (Q, Nw) uint32, counts (Q,) int32)``,
        eager, in request order (see
        :func:`repro.serve.step.make_bitmap_query_step`)."""
        def query_step(queries: Sequence):
            return self.query_many(queries).materialize()
        return query_step

    def serve(self, **config):
        """Open a :class:`repro.serve.service.BitmapService` over this
        session: an async ``submit()/drain()/close()`` port whose
        micro-batch scheduler coalesces concurrently submitted queries
        into the bucketed executors, runs store maintenance (spill /
        compaction / gc) on a background thread, and duty-cycles into a
        standby state when idle — the paper's operating model as a
        serving API.  Keyword arguments go to
        :class:`repro.serve.service.ServiceConfig`."""
        from repro.serve.service import BitmapService
        return BitmapService.open(self, **config)

    def __repr__(self) -> str:
        mode = ("live" if self._si is not None and self.store is None
                else "durable" if self._si is not None else "read-only")
        sch = self.schema or f"{self.num_keys} raw keys"
        return (f"<BitmapDB {mode} {sch} records={self.num_records} "
                f"backend={self.backend}>")


def open_db(path: str, schema: Schema | None = None, *,
            num_keys: int | None = None, backend: str = "auto",
            spill_records: int | None = 4096,
            capacity_words: int = 16) -> BitmapDB:
    """Functional alias of :meth:`BitmapDB.open` — exported as
    ``repro.db.open`` / ``repro.open`` (the documented entry point); named
    ``open_db`` here so this module keeps the ``open`` builtin."""
    return BitmapDB.open(path, schema, num_keys=num_keys, backend=backend,
                         spill_records=spill_records,
                         capacity_words=capacity_words)
