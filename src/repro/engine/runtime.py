"""Streaming multi-core runtime: sharded index builds fused with the elastic
energy model, and incremental append into existing packed indexes.

The paper's Fig. 4 deployment feeds Z independent BIC cores from external
memory and powers idle cores down.  The seed simulated the energy side
(``ElasticScheduler``) separately from the execution side
(``multicore_create_index``); this module fuses them:

  * :func:`multicore_create_index` — shard_map dispatch of the full BIC
    pipeline, one engine backend per device, no cross-core communication
    during indexing (moved here from ``core/elastic.py``; that module keeps
    a thin compatibility wrapper).
  * :class:`StreamingIndexer` — incremental append of record blocks into an
    existing packed index with NO full rebuild: each block is indexed alone
    and bit-spliced onto the packed tail (a shift/carry merge when the
    current record count is not 32-aligned).  The splice runs **jitted
    against a geometrically grown capacity buffer** with the record count
    traced, so steady-state appends of a given block size reuse ONE trace
    instead of re-dispatching an unjitted splice per block;
    :meth:`StreamingIndexer.append_many` goes further and indexes a whole
    batch of blocks in one backend dispatch, folding all the splices in a
    single jitted ``lax.scan``.
  * :class:`MulticoreRuntime` — drives ticks of a workload stream through
    the sharded build AND integrates active/standby energy with the
    calibrated silicon model.  ``run_tick(queries=...)`` additionally serves
    a batch of predicate trees against the freshly built tick index through
    :mod:`repro.engine.batch`.  Every tick's dispatch is wall-clock
    measured and folded into a throughput EWMA; with
    ``calibrate_energy=True`` the elastic model charges active energy over
    the *measured* busy time and re-derives its per-core batch time from
    the measured MB/s — joules track the actual device, not only the paper
    clock.  With ``store_dir=...`` the runtime additionally maintains one
    durable per-core index (``repro.store.SegmentStore`` per core):
    per-batch block indexes splice into per-core streaming indexers, spill
    to segments at the flush threshold, and a restarted runtime recovers
    them bit-identically from manifest + WAL.
  * ``StreamingIndexer.attach_store`` / ``spill`` / ``restore`` — the
    durability hooks: raw blocks are WAL-logged *before* the in-memory
    splice, the tail past the durable prefix flushes as an immutable
    segment (extracted at its unaligned offset by
    :func:`repro.engine.policy.extract_packed`), and recovery replays
    committed segments + surviving WAL blocks into a bit-identical index.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.engine import backends, batch as engine_batch, policy
from repro.core.bic import BICConfig, PaperConfig
from repro.core.elastic import ElasticScheduler, EnergyReport, PowerState
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.obs.energy import EnergyLedger

_RECORDS_INDEXED = _obs_metrics.GLOBAL.counter(
    "engine_records_indexed_total",
    "records appended through streaming indexers")


# ------------------------------------------------------------- sharded build
def multicore_create_index(records: jax.Array, keys: jax.Array,
                           mesh: Mesh, axis: str = "data",
                           *, backend: str = "auto") -> jax.Array:
    """records (Z*B, N, W) sharded over ``axis``; keys replicated.

    Each device runs the full BIC pipeline on its local batches — the
    paper's Fig. 4 dataflow (no cross-core communication during indexing;
    results are resharded only on readout).  Batch counts that do not
    divide the mesh axis are zero-padded for dispatch and sliced off the
    result.  Returns (Z*B, M, ceil(N/32)).
    """
    be = backends.get_backend(backend)
    zb = records.shape[0]
    z = dict(mesh.shape)[axis]
    pad = -zb % z
    if pad:
        records = jnp.pad(records, ((0, pad), (0, 0), (0, 0)))

    def per_core(rec_block, keys_rep):
        return jax.vmap(lambda rec: be.create_index(rec, keys_rep))(rec_block)

    fn = jax.shard_map(
        per_core, mesh=mesh,
        in_specs=(P(axis, None, None), P()),
        out_specs=P(axis, None, None),
        # the Pallas kernels' out_shape structs carry no `vma` (which mesh
        # axes the output varies over), which check_vma=True requires
        check_vma=False)
    out = fn(records, keys)
    return out[:zb] if pad else out


# -------------------------------------------------------- incremental append
_U32 = jnp.uint32


# The shift/carry merge itself lives in :func:`repro.engine.policy
# .splice_packed` (shared with the segment-parallel OR-fold in
# ``engine.batch``); this module owns the jitted entry points.
_splice = jax.jit(policy.splice_packed)


@functools.partial(jax.jit, static_argnames="block_records")
def _fold_scan(buf, num_records0, blocks, block_records):
    """Fold B uniform block splices into the capacity buffer in one trace."""
    def body(carry, block):
        cbuf, n = carry
        return (policy.splice_packed(cbuf, n, block), n + block_records), None

    carry, _ = jax.lax.scan(body, (buf, num_records0), blocks)
    return carry


@functools.lru_cache(maxsize=8)
def _vmapped_create(backend_name: str):
    """One jitted vmapped create_index per backend: a whole batch of record
    blocks indexes in a single dispatch."""
    be = backends.get_backend(backend_name)
    return jax.jit(jax.vmap(be.create_index, in_axes=(0, None)))


def splice_cache_size() -> int:
    """Number of compiled splice traces (exposed for tests/benchmarks: a
    steady-state append stream must NOT grow this per block)."""
    return _splice._cache_size()


def append_packed(packed: jax.Array, num_records: int,
                  block: jax.Array, block_records: int) -> jax.Array:
    """Bit-splice a freshly indexed ``block`` (M, ceil(n'/32)) onto a packed
    index (M, ceil(n/32)) holding ``num_records`` records.

    Pad bits past each logical record count must be zero (every engine
    backend guarantees this).  O(words) jitted shift/carry merge — no
    unpack; the trace is cached by word-count shape only (the record count
    enters traced).
    """
    total_words = policy.num_words(num_records + block_records)
    slack = block.shape[1] + 1           # splice window past the tail word
    buf = jnp.pad(packed, ((0, 0), (0, slack)))
    return _splice(buf, jnp.int32(num_records), block)[:, :total_words]


class StreamingIndexer:
    """Grow one key-major index record-block by record-block.

    ``append`` indexes only the incoming block and splices it in; the live
    index is always available via ``.index`` (bit-identical to a
    from-scratch rebuild over all records seen so far).  The packed words
    live in a geometrically doubled capacity buffer so the jitted splice
    keeps one trace per block size instead of re-tracing as the index
    grows; size ``capacity_words`` for the expected stream to avoid growth
    retraces entirely.

    With a :class:`repro.store.SegmentStore` attached the index outlives
    the process: every incoming block is WAL-logged *before* the in-memory
    splice, the tail past the store's durable prefix flushes as an
    immutable segment once ``flush_records`` accumulate (or on an explicit
    :meth:`spill`), and :meth:`restore` rebuilds a bit-identical live
    indexer from manifest + WAL after a crash.
    """

    def __init__(self, keys: jax.Array, *, backend: str = "auto",
                 capacity_words: int = 16):
        self.keys = jnp.asarray(keys, jnp.int32)
        self.backend = backends.resolve_backend(backend)
        self._cap = max(int(capacity_words), 2)
        # the buffer lives where the keys were committed (a session pinned
        # to one device); uncommitted keys leave placement to JAX
        self._buf = jnp.zeros((self.keys.shape[0], self._cap), jnp.uint32,
                              device=(self.keys.sharding
                                      if self.keys.committed else None))
        self._num_records = 0
        self._store = None
        self._flush_records: int | None = None
        self._last_tick = -1
        self._last_tick_blocks = 0
        # guards the (WAL log, buf, num_records, tick) commit point of an
        # append against concurrent snapshot readers (background spill /
        # serving view).  Held only for the splice DISPATCH and field
        # assignments — never for device work, segment writes, or merges,
        # so appends don't wait on maintenance and vice versa.
        self._mu = threading.RLock()
        # background-maintenance tap: when set, a reached flush threshold
        # calls the hook (enqueue work) instead of spilling synchronously
        # on the append path
        self._spill_hook: Callable[[], None] | None = None

    @property
    def num_records(self) -> int:
        return self._num_records

    @property
    def store(self) -> "SegmentStore":
        return self._store

    @property
    def last_tick(self) -> int:
        """Highest ``tick`` stamp this index has absorbed (-1 when ticks
        are untracked).  Survives spill/crash/restore."""
        return self._last_tick

    def absorbed_blocks(self, tick: int) -> int:
        """How many blocks of (monotone) workload tick ``tick`` this index
        has already absorbed — the replay-idempotence watermark a driver
        uses to skip the already-applied prefix of an in-flight tick.
        Returns -1 when ``tick`` is below the watermark entirely (every
        block of it was absorbed before a later tick started)."""
        if tick == self._last_tick:
            return self._last_tick_blocks
        return 0 if tick > self._last_tick else -1

    def _stamp_tick(self, tick: int | None) -> None:
        if tick is None:
            return
        if tick > self._last_tick:
            self._last_tick, self._last_tick_blocks = tick, 1
        elif tick == self._last_tick:
            self._last_tick_blocks += 1

    def _grow(self, need_words: int) -> None:
        if need_words > self._cap:
            new = self._cap
            while new < need_words:
                new *= 2
            self._buf = jnp.pad(self._buf, ((0, 0), (0, new - self._cap)))
            self._cap = new

    # ----------------------------------------------------------- durability
    def attach_store(self, store: "SegmentStore", *,
                     flush_records: int | None = 4096) -> None:
        """Make this index durable: WAL-log every future append into
        ``store`` and auto-:meth:`spill` a segment whenever the in-memory
        tail reaches ``flush_records`` records (None = manual spills only).

        The store must not be ahead of the indexer — to resume from a
        non-empty store, use :meth:`restore` instead."""
        store.ensure_keys(np.asarray(jax.device_get(self.keys)))
        wal_tail = store.replay_wal()
        if store.durable_records > self._num_records or wal_tail:
            # ahead in segments OR carrying an unflushed WAL tail: a fresh
            # attach would log conflicting blocks at already-claimed
            # offsets and make the store unrecoverable
            raise ValueError(
                f"store already holds {store.durable_records} durable "
                f"records and {len(wal_tail)} WAL tail blocks; "
                "use StreamingIndexer.restore to resume from a store")
        self._store = store
        self._flush_records = flush_records
        if self._num_records > store.durable_records:
            # records indexed before the attach were never WAL-logged —
            # flush them now so recovery has no gap below the WAL floor
            self.spill()

    def _flush_snapshot(self):
        """Consistent (tail, count, start, tick watermark) snapshot of the
        flushable suffix — the indexer mutex pins (buf, count, watermark)
        together so a snapshot taken mid-append can never pair a new
        buffer with an old count (or a watermark that over/under-claims
        the flushed blocks)."""
        with self._mu:
            start = self._store.durable_records
            count = self._num_records - start
            if count <= 0:
                return None
            buf = self._buf
            wm = (self._last_tick, self._last_tick_blocks)
        # extraction runs OUTSIDE the mutex: the captured buffer is a
        # functional jax array, and extract_packed can pay a first-sight
        # jit compile — holding the lock here would stall every
        # concurrent append behind the background spill
        return policy.extract_packed(buf, start, count), count, start, wm

    def spill(self) -> None:
        """Flush the in-memory tail past the store's durable prefix as one
        immutable segment (atomic manifest commit + WAL rotation).  A
        no-op when nothing new has arrived since the last spill."""
        if self._store is None:
            raise RuntimeError("no store attached (see attach_store)")
        snap = self._flush_snapshot()
        if snap is None:
            return
        tail, count, start, wm = snap
        with _obs_trace.maybe_span("spill", records=count):
            self._store.write_segment(
                np.asarray(jax.device_get(tail)), count, start,
                tick_watermark=wm)

    # ------------------------------------------------- background spill
    def set_spill_hook(self, hook: Callable[[], None] | None) -> None:
        """Route threshold-triggered flushes through ``hook()`` (e.g. a
        maintenance executor's enqueue) instead of spilling synchronously
        on the append path; ``None`` restores synchronous spills.  The
        hook runs on the appending thread and must only enqueue."""
        self._spill_hook = hook

    def pending_flush_records(self) -> int:
        """Records in memory past the store's durable prefix (0 when no
        store is attached) — what a background flush would spill."""
        with self._mu:
            if self._store is None:
                return 0
            return self._num_records - self._store.durable_records

    def prepare_spill(self):
        """Background-flush phase one: snapshot the flushable tail and
        write its segment FILE (the slow part — runs on a maintenance
        thread; concurrent appends keep streaming into the WAL).  Returns
        an opaque token for :meth:`commit_spill`, or None when nothing
        needs flushing.  Crash before the commit: the file is an orphan,
        the WAL still holds every block — recovery is unaffected."""
        if self._store is None:
            raise RuntimeError("no store attached (see attach_store)")
        snap = self._flush_snapshot()
        if snap is None:
            return None
        tail, count, start, wm = snap
        with _obs_trace.maybe_span("spill.prepare", records=count):
            meta = self._store.prepare_segment(
                np.asarray(jax.device_get(tail)), count, start)
        return meta, wm

    def commit_spill(self, token) -> None:
        """Background-flush phase two: atomic manifest swap making the
        prepared segment live.  Blocks appended during phase one are
        carried into the fresh WAL generation by the store before the
        swap (see ``SegmentStore._commit``)."""
        meta, wm = token
        with _obs_trace.maybe_span("spill.commit", file=meta.file):
            self._store.commit_segment(meta, tick_watermark=wm)

    def abort_spill(self, token) -> None:
        """Abandon a prepared spill (its orphan file becomes gc fodder)."""
        self._store.abort_segment(token[0])

    def _maybe_spill(self) -> None:
        if (self._store is not None and self._flush_records is not None
                and (self._num_records - self._store.durable_records
                     >= self._flush_records)):
            if self._spill_hook is not None:
                self._spill_hook()
            else:
                self.spill()

    def _log_block(self, records, start: int,
                   tick: int | None = None) -> None:
        if self._store is not None:
            if not isinstance(records, np.ndarray):     # a device array
                records = np.asarray(jax.device_get(records))
            self._store.log_block(records, start, tick)

    @classmethod
    def restore(cls, store, keys, *, backend: str = "auto",
                capacity_words: int = 16,
                flush_records: int | None = 4096) -> "StreamingIndexer":
        """Crash recovery: load the committed segments, re-index the
        surviving WAL blocks (backends are pure functions of their
        inputs), and splice them on — the result is bit-identical to the
        pre-crash in-memory index, with the store re-attached for further
        appends."""
        si = cls(keys, backend=backend, capacity_words=capacity_words)
        store.ensure_keys(np.asarray(jax.device_get(si.keys)))
        m = store.manifest
        si._last_tick = m.last_tick
        si._last_tick_blocks = m.last_tick_blocks
        packed, n = store.load_packed()
        if n:
            si._grow(packed.shape[1] + 1)
            si._buf = si._buf.at[:, :packed.shape[1]].set(jnp.asarray(packed))
            si._num_records = n
        be = backends.get_backend(si.backend)
        for start, rec, tick in store.replay_wal():
            if start != si._num_records:
                raise ValueError(
                    f"WAL block starts at record {start} but the recovered "
                    f"stream position is {si._num_records}")
            block = be.create_index(jnp.asarray(rec), si.keys)
            si._grow(start // policy.PACK + block.shape[1] + 1)
            si._buf = _splice(si._buf, jnp.int32(start), block)
            si._num_records += rec.shape[0]
            si._stamp_tick(tick)
        # attach AFTER replay: replayed blocks are already in the WAL
        si._store = store
        si._flush_records = flush_records
        return si

    # --------------------------------------------------------------- append
    def append(self, records: jax.Array) -> policy.BitmapIndex:
        """Index a (N', W) record block and splice it in; returns the
        updated live index.  An empty block is a no-op (no dispatch)."""
        n_new = int(records.shape[0])
        if n_new == 0:
            return self.index
        block = backends.get_backend(self.backend).create_index(
            records, self.keys)
        self.append_indexed(records, block)
        return self.index

    def append_indexed(self, records, block: jax.Array, *,
                       tick: int | None = None) -> None:
        """Splice in a block whose (M, ceil(N'/32)) index ``block`` was
        already built elsewhere (e.g. by a sharded tick dispatch) — the raw
        ``records`` (host or device) are still WAL-logged so recovery can
        re-index them.  ``tick`` stamps the block for replay idempotence
        (see :attr:`last_tick`).  Returns nothing: read :attr:`index` or
        :meth:`view` for the result (each :attr:`index` is a fresh slice
        of the capacity buffer)."""
        n_new = int(records.shape[0])
        if n_new == 0:
            return
        with self._mu:     # log + splice + count + tick commit atomically
            self._log_block(records, self._num_records, tick)
            self._grow(self._num_records // policy.PACK
                       + block.shape[1] + 1)
            self._buf = _splice(self._buf, jnp.int32(self._num_records),
                                block)
            self._num_records += n_new
            self._stamp_tick(tick)
        _RECORDS_INDEXED.add(n_new)
        self._maybe_spill()

    def append_many(self, records: jax.Array, *, mesh: Mesh | None = None,
                    axis: str = "data") -> policy.BitmapIndex:
        """Append a batch of uniform blocks (B, N', W) in two dispatches:
        one vmapped index build (sharded over ``mesh`` when given) and one
        ``lax.scan`` that folds all B splices."""
        b, n_blk = int(records.shape[0]), int(records.shape[1])
        if b == 0 or n_blk == 0:
            return self.index
        if mesh is not None:
            blocks = multicore_create_index(records, self.keys, mesh, axis,
                                            backend=self.backend)
        else:
            blocks = _vmapped_create(self.backend)(records, self.keys)
        # the device readback depends on nothing the mutex guards — keep
        # snapshot readers (serving views) unblocked during the transfer
        host = (np.asarray(jax.device_get(records))
                if self._store is not None else None)
        with self._mu:     # log + fold + count commit atomically
            if host is not None:
                for i in range(b):
                    self._store.log_block(host[i],
                                          self._num_records + i * n_blk)
            total = self._num_records + b * n_blk
            self._grow(total // policy.PACK + blocks.shape[2] + 1)
            self._buf, _ = _fold_scan(self._buf,
                                      jnp.int32(self._num_records),
                                      blocks, n_blk)
            self._num_records = total
        _RECORDS_INDEXED.add(b * n_blk)
        self._maybe_spill()
        return self.index

    def view(self) -> tuple[jax.Array, int]:
        """A consistent (capacity buffer, record count) pair even under a
        concurrent append — the serving snapshot :mod:`repro.db` caches
        on.  The buffer is a functional jax array, so the pair stays a
        bit-exact point-in-time view of the stream forever."""
        with self._mu:
            return self._buf, self._num_records

    @property
    def index(self) -> policy.BitmapIndex:
        buf, n = self.view()
        return policy.BitmapIndex(buf[:, :policy.num_words(n)], n)


def fold_block_indexes(blocks: jax.Array,
                       block_records: int) -> policy.BitmapIndex:
    """Fold per-block indexes (B, M, BW) of uniform ``block_records``-record
    blocks into ONE packed index over the concatenated records (a single
    scanned splice dispatch) — e.g. the output of
    :func:`multicore_create_index` becoming a servable tick index."""
    b, m, bw = blocks.shape
    total = b * block_records
    buf = jnp.zeros((m, total // policy.PACK + bw + 1), jnp.uint32)
    (buf, _) = _fold_scan(buf, jnp.int32(0), blocks, block_records)
    return policy.BitmapIndex(buf[:, :policy.num_words(total)], total)


# ------------------------------------------------- fused execution + energy
@dataclasses.dataclass
class TickResult:
    indexes: jax.Array | None   # (B_t, M, ceil(N/32)); None on an idle tick
    active_cores: int
    report: EnergyReport
    query_rows: jax.Array | None = None     # (Q, ceil(B_t*N/32)) uint32
    query_counts: jax.Array | None = None   # (Q,) int32
    measured_seconds: float = 0.0           # wall-clock of the tick dispatch
    # record MB/s of THIS dispatch, in PAPER units: one 8-bit record word
    # = one byte (matching bic_create_cpu and the elastic cycle model),
    # regardless of the int32 container the words travel in
    measured_mbps: float = 0.0


class MulticoreRuntime:
    """Sharded indexing with elastic energy accounting in one place.

    Each call to :meth:`run_tick` dispatches one tick's record batches over
    the mesh (reusing :func:`multicore_create_index`) and charges the
    elastic scheduler's calibrated power model for the cores the *policy*
    would activate (``cores_needed``); idle cores accrue standby energy
    (CG / CG+RBB).

    Every dispatch is wall-clock measured and folded into a throughput
    EWMA (``measured_mbps``).  By default joules still follow the
    paper-clock model; with ``calibrate_energy=True`` the measured busy
    time replaces the model's busy time for active energy AND the
    scheduler's per-core batch time is re-derived from the measured MB/s
    (``ElasticScheduler.calibrate``), so both joules and the activation
    policy track the device actually running the dispatch.

    With ``store_dir=...`` the runtime keeps one durable index per core:
    tick batches are assigned round-robin to per-core
    :class:`StreamingIndexer`\\ s (splicing the already-built per-batch
    block indexes — no re-indexing), each backed by its own
    ``repro.store.SegmentStore`` under ``<store_dir>/core-<z>`` with
    WAL-before-splice durability and ``flush_records`` segment spills.  A
    restarted runtime pointed at the same directory recovers every
    per-core index bit-identically (manifest + WAL replay).
    """

    def __init__(self, mesh: Mesh, axis: str = "data",
                 cfg: BICConfig = PaperConfig,
                 state: PowerState = PowerState(), *,
                 backend: str = "auto", calibrate_energy: bool = False,
                 store_dir: str | None = None, flush_records: int = 4096,
                 throughput_ewma: float = 0.5):
        self.mesh = mesh
        self.axis = axis
        self.backend = backends.resolve_backend(backend)
        self.num_cores = dict(mesh.shape)[axis]
        self.scheduler = ElasticScheduler(self.num_cores, cfg, state)
        self.report = EnergyReport()
        # joule ledger on the same operating points: tick reports feed
        # it so ingest energy rolls up to pJ-per-indexed-bit
        self.ledger = EnergyLedger(self.scheduler)
        self.calibrate_energy = calibrate_energy
        self.store_dir = store_dir
        self.flush_records = flush_records
        self.throughput_ewma = throughput_ewma
        self.measured_mbps = 0.0            # EWMA over non-idle ticks
        self._core_si: list[StreamingIndexer] | None = None

    def bind_ledger(self, ledger: EnergyLedger) -> None:
        """Rebind tick charging to a shared ledger (a serving stack's —
        see :meth:`repro.serve.service.BitmapService.attach_runtime`):
        indexing and serving then roll up into ONE energy report, and
        the shared ledger's attributed+unattributed invariant still
        holds because every tick's joules enter through its
        ``charge_report``."""
        self.ledger = ledger

    # ---------------------------------------------------- per-core indexes
    def core_indexers(self, keys: jax.Array) -> list[StreamingIndexer]:
        """The per-core durable indexers (created, or recovered from the
        store, on first use).  Requires ``store_dir``; every call must use
        the SAME keys the indexers were created with."""
        if self.store_dir is None:
            raise RuntimeError("MulticoreRuntime has no store_dir")
        if self._core_si is not None:
            cached = self._core_si[0].keys
            keys32 = jnp.asarray(keys, jnp.int32)
            if (cached.shape != keys32.shape
                    or not bool(jnp.all(cached == keys32))):
                raise ValueError(
                    "per-core indexers were created with a different key "
                    "set; a runtime persists ONE key set per store_dir")
        if self._core_si is None:
            from repro.store import SegmentStore
            sis = []
            for z in range(self.num_cores):
                st = SegmentStore(os.path.join(self.store_dir, f"core-{z}"))
                if st.durable_records or st.replay_wal():
                    si = StreamingIndexer.restore(
                        st, keys, backend=self.backend,
                        flush_records=self.flush_records)
                else:
                    si = StreamingIndexer(keys, backend=self.backend)
                    si.attach_store(st, flush_records=self.flush_records)
                sis.append(si)
            self._core_si = sis
        return self._core_si

    def core_indexes(self, keys: jax.Array) -> list[policy.BitmapIndex]:
        """The live per-core cumulative indexes (recovering from the store
        first if this runtime has not ticked yet)."""
        return [si.index for si in self.core_indexers(keys)]

    def checkpoint(self) -> None:
        """Force-spill every per-core in-memory tail to its segment store
        (e.g. before a planned shutdown)."""
        for si in self._core_si or ():
            si.spill()

    def run_tick(self, records: jax.Array | None, keys: jax.Array,
                 tick_seconds: float, *,
                 queries: Sequence | None = None,
                 tick_id: int | None = None) -> TickResult:
        """records (B_t, N, W) for this tick (None = idle tick).

        ``queries`` — an optional batch of predicate trees (or pre-built
        plans) served against the index of THIS tick's records: the
        per-core block indexes fold into one packed tick index (scanned
        splice) and the whole batch executes through
        :func:`repro.engine.batch.execute_many` in a few bucketed
        dispatches.  Results land in ``TickResult.query_rows/query_counts``
        in query order.

        ``tick_id`` (monotone) makes the durable per-core appends
        **idempotent under replay**: the id is WAL-stamped with every
        block and survives spill/crash/restore, so re-feeding the tick
        that was in flight at crash time appends only to the cores that
        had not absorbed it yet.  Without ``tick_id`` the driver owns
        exactly-once tick delivery."""
        wl = 0 if records is None else records.shape[0]
        if wl == 0:
            tick = self.scheduler.account(0, tick_seconds)
            self.report.merge(tick)
            self.ledger.charge_report(tick)
            return TickResult(None, 0, tick)
        t0 = time.perf_counter()
        out = multicore_create_index(records, keys, self.mesh, self.axis,
                                     backend=self.backend)
        jax.block_until_ready(out)
        elapsed = max(time.perf_counter() - t0, 1e-9)
        # paper units: one 8-bit record word = one byte (see TickResult)
        mbps = wl * records.shape[1] * records.shape[2] / 1e6 / elapsed
        a = self.throughput_ewma
        self.measured_mbps = (mbps if self.measured_mbps == 0.0
                              else a * mbps + (1 - a) * self.measured_mbps)
        if self.calibrate_energy:
            self.scheduler.calibrate(self.measured_mbps / self.num_cores)
            tick = self.scheduler.account(
                wl, tick_seconds, busy_seconds=min(elapsed, tick_seconds))
        else:
            tick = self.scheduler.account(wl, tick_seconds)
        self.report.merge(tick)
        self.ledger.charge_report(tick)
        # one indexed bit per (record, key) pair this tick produced
        self.ledger.attribute_bits(wl * records.shape[1] * keys.shape[0])
        z = self.scheduler.cores_needed(wl, tick_seconds)
        if self.store_dir is not None:
            sis = self.core_indexers(keys)
            # crash-replayed tick: each core skips the blocks it already
            # absorbed (a core can hold several batches per tick, so the
            # watermark is (tick, blocks), not just the tick id)
            todo: list[tuple[StreamingIndexer, list[int]]] = []
            for core in range(self.num_cores):
                done = (sis[core].absorbed_blocks(tick_id)
                        if tick_id is not None else 0)
                if done < 0:
                    continue
                blocks = list(range(core, wl, self.num_cores))[done:]
                if blocks:
                    todo.append((sis[core], blocks))
            if todo:                     # one D2H transfer, skipped when
                host = np.asarray(jax.device_get(records))   # fully replayed
                for si, blocks in todo:
                    for b in blocks:
                        si.append_indexed(host[b], out[b], tick=tick_id)
        qrows = qcounts = None
        if queries is not None and len(queries):
            idx = fold_block_indexes(out, records.shape[1])
            qrows, qcounts = engine_batch.execute_many(
                idx.packed, queries, num_records=idx.num_records,
                backend=self.backend)
        return TickResult(out, z, tick, qrows, qcounts,
                          measured_seconds=elapsed, measured_mbps=mbps)

    def index_stream(self, ticks: Iterable[jax.Array | None],
                     keys: jax.Array, tick_seconds: float
                     ) -> tuple[list[jax.Array], EnergyReport]:
        """Run a whole workload stream; returns per-tick index arrays and
        the energy report for THIS stream (the runtime-lifetime total stays
        available as ``self.report``)."""
        outputs = []
        stream_report = EnergyReport()
        for records in ticks:
            res = self.run_tick(records, keys, tick_seconds)
            stream_report.merge(res.report)
            if res.indexes is not None:
                outputs.append(res.indexes)
        return outputs, stream_report
