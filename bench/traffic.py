"""The general traffic generators a mix names in its ``"generator"`` key.

A generator builds the cell's data from the seed, sets the program up, runs
the measured window, and compares what the timed path produced with the
configuration's plain reference.  Every parameter of the traffic comes from
the mix file; everything about the data comes from the configuration's
JSON file and its reference module.

``open_loop``: seeded open-loop arrivals of filter queries against
``BitmapDB.serve()``.  One generator thread submits each query at its due
time; one collector thread waits on the futures in submission order and
stamps each as it resolves; one reader thread then reads each answer's
``.count`` (and takes the sampled row sets, copied to the host after the
window).  Latency runs from the due time to the stamp, so a stall counts
against every query it delays, and no read delays a later stamp.

``closed_loop``: ``streams`` clients, each with exactly one query
outstanding against ``BitmapDB.serve()``.  Each runs the templates in its
own seeded order, repeated, with fresh parameters per query, and submits
its next query as soon as it has read the ``.count`` of the last one: no
think time.  Each stream is a client thread of its own, so no client's
read waits for another's.  A client records its times and counts in
arrays and keeps no Python object per query; the window's queries are
drawn again from the streams' seeds for the comparison.  The end-to-end
metric is the queries whose count was read inside the window, per
second.

``load``: record blocks appended back to back through
``BitmapDB.append_encoded`` from a host pool, a fresh session every
``session_records``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import heapq
import itertools
import queue
import threading
import time

import numpy as np

from bench import reference

#: how long past the window's close an answer is still waited for: a late
#: answer is late, not wrong
LATE_S = 60.0


@dataclasses.dataclass
class Check:
    """One number of the correctness comparison and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def sub_rng(seed: int, stream: str, *sub: int) -> np.random.Generator:
    """One of many independent generators of ``stream`` for ``seed``
    (``sub``: non-negative integers, such as a client's number); none is
    any generator of :func:`reference.rng`, whose keys are shorter."""
    return np.random.default_rng([reference.STREAMS[stream],
                                  seed % (1 << 64), (seed >> 64) % (1 << 64),
                                  *sub])


def pow2_upto(cap: int) -> list:
    out, s = [], 1
    while s < cap:
        out.append(s)
        s *= 2
    return out + [cap]


class Traffic:
    """Shared plumbing: the configuration, mix, seed and the run's hooks
    (``run.span`` for harness spans, ``run.window_started`` to start the
    profiler)."""

    def __init__(self, config, mix: dict, seed: int, run):
        self.cfg = config.sizes
        self.ref = config.module
        self.mix = mix
        self.seed = seed
        self.run = run
        self.phases: dict = {}
        self.notes: dict = {}          # extra numbers for the stderr lines

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        with self.run.span(f"setup.{name}"):
            yield
        self.phases[name] = time.perf_counter() - t0


# ------------------------------------------------------- filter queries
def to_expr(query):
    """A reference query (tuple of predicates) as the program's DSL
    expression, in the form the SQL states it."""
    from repro.db import col

    def one(pred):
        op, c = pred[0], col(pred[1])
        if op == "eq":
            return c == pred[2]
        if op == "in":
            return c.isin(pred[2])
        if op == "between":
            return c.between(pred[2], pred[3])
        if op == "lt":
            return c < pred[2]
        raise ValueError(f"unknown predicate op {op!r}")

    return functools.reduce(lambda a, b: a & b, [one(p) for p in query])


@dataclasses.dataclass
class Schedule:
    """One open-loop window: due times (s from its start), queries and
    their templates."""
    due: np.ndarray
    queries: list
    templates: list
    gaps: np.ndarray


@dataclasses.dataclass
class Served:
    """What the collector saw of one window."""
    t_start: float
    t_sub: np.ndarray
    t_done: np.ndarray
    counts: np.ndarray
    failed: np.ndarray
    rows: dict
    trace_ids: dict
    t_close: float


class FilterTraffic(Traffic):
    """Filter queries against ``BitmapDB.serve()``: what the open and the
    closed loop share.  The data, the service and its warm-up, the
    reference, the control's answers, the comparison and the bytes a query
    must move are here; a subclass runs the window and leaves ``window``
    (the :class:`Schedule` of the queries compared), ``sample`` (those
    whose row sets are compared) and ``served`` (the :class:`Served`
    answers)."""

    def make_data(self) -> None:
        """The generated rows and the column domains (set-up phase
        ``data``); all the control needs."""
        with self.phase("data"):
            self.rows = self.ref.generate(self.cfg, self.seed)
            self.domains = self.ref.columns(self.cfg)
        self.templates = (self.mix.get("templates")
                          or list(self.ref.TEMPLATES))
        self.nw = -(-len(next(iter(self.rows.values()))) // 32)

    def start_service(self) -> None:
        """Data, ingest, ``serve()``, and the warm-up of every
        one-template bucket and every read the traffic reaches (set-up
        phases ``data``, ``ingest``, ``warmup``, ``warm_reads``)."""
        import jax

        import repro
        from repro.db import Column, Schema

        cfg, ref = self.cfg, self.ref
        self.make_data()
        schema = Schema([Column.categorical(name, vals)
                         for name, vals in self.domains])
        n = len(next(iter(self.rows.values())))
        block = cfg["block_records"]
        with self.phase("ingest"):
            db = repro.BitmapDB(schema, backend="auto",
                                capacity_words=self.nw + block // 32 + 1)
            for s in range(0, n, block):
                db.append({c: v[s:s + block] for c, v in self.rows.items()})
            jax.block_until_ready(db.indexer.view()[0])
        self.db = db
        self.svc = db.serve()
        r = reference.rng(self.seed, "queries")
        exprs = [to_expr(ref.draw(cfg, r, t)) for t in self.templates]
        sizes = pow2_upto(min(int(self.mix["warm_max_q"]),
                              self.svc.config.max_batch))
        with self.phase("warmup"):
            # every template alone at every power-of-two bucket size the
            # traffic reaches, through the session's own path (the cost
            # model picks the backend and the factoring, as in a wave)
            failures = []
            for e in exprs:
                for s in sizes:
                    try:
                        rows, counts = db.query_many(
                            [e] * s, pad_output=True).materialize()
                        jax.block_until_ready(rows)
                    except Exception as err:    # noqa: BLE001 — reported
                        failures.append(f"{e!r:.60} x{s}: {err!s:.200}")
            self.notes["warm_failures"] = len(failures)
            for f in failures:
                print(f"warmup failed: {f}", flush=True)
        with self.phase("warm_reads"):
            # the client's reads, .count and .rows, index one wave's
            # arrays at one position each: warm every (wave size,
            # position) the service can hand out
            for s in pow2_upto(self.svc.config.max_batch):
                rows, counts = db.query_many([exprs[0]] * s,
                                             pad_output=True).materialize()
                for qi in range(s):
                    counts[qi].block_until_ready()
                    rows[qi].block_until_ready()
            del rows, counts

    def release(self) -> None:
        h = self.svc.health()
        self.notes.update({k: h[k] for k in (
            "fallback_queries", "degraded_waves", "wave_retries",
            "isolated_failures", "deadline_rejected")})
        m = self.svc.metrics()
        self.notes.update(waves=m.batches, wave_mean=m.batch_mean,
                          wave_max=m.batch_max)
        self.svc.close()
        del self.svc, self.db
        gc.collect()

    @property
    def attempted(self) -> int:
        return len(self.window.queries)

    @property
    def failed(self) -> int:
        return int(self.served.failed.sum())

    def answers(self, control: bool = False):
        """(counts, sampled rows) of the window: the program's, or with
        ``control`` the control's (the reference with ranges answered as
        binned supersets) put in the program's place."""
        if not control:
            return self.served.counts, self.served.rows
        fr = self._reference()
        w = self.ref.CONTROL_BIN
        counts = np.array([fr.count(q, w) for q in self.window.queries])
        return counts, {i: fr.row(self.window.queries[i], w)
                        for i in self.sample}

    def _reference(self):
        fr = getattr(self, "_fr", None)
        if fr is None:
            fr = self._fr = reference.FilterReference(dict(self.domains),
                                                      self.rows)
        return fr

    def check(self, control: bool = False) -> list:
        fr = self._reference()
        counts, rows = self.answers(control)
        qs = self.window.queries
        want = np.array([fr.count(q) for q in qs])
        ok = (np.ones(len(qs), bool) if control
              else ~self.served.failed)
        bad_rows = sum(1 for i, row in rows.items()
                       if not np.array_equal(row, fr.row(qs[i])))
        return [Check("unanswered", float(len(qs) - ok.sum()), 0),
                Check("count_mismatches",
                      float(np.count_nonzero((counts != want) & ok)), 0),
                Check("row_mismatches", float(bad_rows), 0)]

    def query_bytes(self, idx) -> float:
        """Bytes the queries ``idx`` must move at least: each distinct key
        row they reference, plus one output row, once."""
        fr = self._reference()
        qs = self.window.queries
        return float(sum(fr.key_rows(qs[i]) + 1 for i in idx)
                     * self.nw * 4)


class OpenLoop(FilterTraffic):
    """Open-loop filter queries at ``rate_per_s`` (see module docstring).

    Mix keys: ``rate_per_s``, ``templates`` (null: all of the
    configuration's), ``warm_max_q`` (widest one-template bucket warmed),
    ``warm_burst_s`` (untimed traffic before the window),
    ``sample_per_template`` (row sets compared per template),
    ``profile_lead_s``/``profile_s`` (the traced part of a ``--trace 1``
    window)."""

    def setup(self) -> None:
        self.start_service()
        with self.phase("burst"):
            burst = self.schedule("burst", self.mix["warm_burst_s"])
            self.drive(burst, sample=set(range(0, len(burst.queries), 7)))

    def schedule(self, stream: str, seconds: float) -> Schedule:
        """``rate_per_s * seconds`` queries whose gaps are the quantiles of
        the exponential distribution in a seeded order (Poisson arrivals,
        the same set of gaps for every seed) and whose templates are
        balanced, in a seeded order."""
        rate = float(self.mix["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        window = stream == "window"
        r = reference.rng(self.seed, "arrivals" if window else "burst")
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        gaps = r.permutation(gaps)
        due = np.cumsum(gaps) - gaps[0]
        k = len(self.templates)
        tmpl = [self.templates[i % k] for i in range(n)]
        tmpl = [tmpl[i] for i in r.permutation(n)]
        qr = reference.rng(self.seed, "queries" if window
                           else "burst_queries")
        queries = [self.ref.draw(self.cfg, qr, t) for t in tmpl]
        return Schedule(due, queries, tmpl, gaps)

    def drive(self, sch: Schedule, sample: set, on_start=None) -> Served:
        """Run one open-loop window of ``sch`` and collect every answer
        (waiting up to :data:`LATE_S` past the last due time)."""
        svc = self.svc
        n = len(sch.queries)
        t_sub = np.full(n, np.nan)
        t_done = np.full(n, np.nan)
        counts = np.full(n, -1, np.int64)
        failed = np.zeros(n, bool)
        rows: dict = {}                # sampled rows, still on the device
        trace_ids: dict = {}
        handoff: queue.SimpleQueue = queue.SimpleQueue()
        resolved: queue.SimpleQueue = queue.SimpleQueue()
        t_start = time.perf_counter() + 0.05
        give_up = t_start + float(sch.due[-1]) + LATE_S
        errors: list = []

        def generate():
            try:
                for i in range(n):
                    d = t_start + sch.due[i] - time.perf_counter()
                    if d > 0:
                        time.sleep(d)
                    t_sub[i] = time.perf_counter()
                    try:
                        # built as a client builds it, when it is sent
                        fut = svc.submit(to_expr(sch.queries[i]))
                    except Exception as e:      # noqa: BLE001 — counted
                        fut = e
                    handoff.put((i, fut))
            finally:
                handoff.put(None)

        def collect():
            try:
                nxt = none = object()          # no item held over
                while True:
                    item = nxt if nxt is not none else handoff.get()
                    nxt = none
                    if item is None:
                        return
                    i, fut = item
                    if isinstance(fut, BaseException):
                        failed[i] = True
                        continue
                    fut.wait(max(0.0, give_up - time.perf_counter()))
                    now = time.perf_counter()
                    got = [(i, fut)]
                    # one wave resolves many futures at once: stamp every
                    # later one already resolved at this same instant
                    while True:
                        try:
                            nxt = handoff.get_nowait()
                        except queue.Empty:
                            break
                        if (nxt is None or isinstance(nxt[1], BaseException)
                                or not nxt[1].done()):
                            break
                        got.append(nxt)
                        nxt = none
                    for j, f in got:
                        if not f.done() or f.exception(0) is not None:
                            failed[j] = True
                            continue
                        t_done[j] = now
                        resolved.put((j, f))
            except BaseException as e:          # noqa: BLE001 — re-raised
                errors.append(e)
            finally:
                resolved.put(None)

        def read():
            # the client's read of each answer, off the stamping thread
            try:
                while (item := resolved.get()) is not None:
                    j, f = item
                    counts[j] = f.count
                    if f.trace_id is not None:
                        trace_ids[f.trace_id] = j
                    if j in sample:
                        rows[j] = f.rows
            except BaseException as e:          # noqa: BLE001 — re-raised
                errors.append(e)

        threads = [threading.Thread(target=generate, name="bench-generator"),
                   threading.Thread(target=collect, name="bench-collector"),
                   threading.Thread(target=read, name="bench-reader")]
        for th in threads:
            th.start()
        if on_start is not None:
            on_start(t_start)
        for th in threads:
            th.join(timeout=max(1.0, give_up - time.perf_counter()) + 30)
            if th.is_alive():
                raise RuntimeError(f"{th.name} did not finish")
        if errors:
            raise errors[0]
        t_close = time.perf_counter()
        return Served(t_start, t_sub, t_done, counts, failed,
                      {j: np.asarray(r) for j, r in rows.items()},
                      trace_ids, t_close)

    def plan_window(self, seconds: float) -> None:
        """The window's schedule and the sample whose rows are compared."""
        self.window = self.schedule("window", seconds)
        self.sample = self.pick_sample(self.window)

    def measure(self, seconds: float) -> None:
        self.plan_window(seconds)
        self.served = self.drive(self.window, self.sample,
                                 on_start=self.run.window_started)
        s = self.served
        self.t0, self.t1 = s.t_start, s.t_start + seconds
        due = s.t_start + self.window.due
        done = np.where(s.failed, s.t_close, s.t_done)
        self.latency_ms = (done - due) * 1e3
        self.lag_ms = (s.t_sub - due) * 1e3

    def pick_sample(self, sch: Schedule) -> set:
        """A seeded sample of queries whose row sets are compared:
        ``sample_per_template`` of each template."""
        r = reference.rng(self.seed, "sample")
        k = int(self.mix["sample_per_template"])
        out = set()
        for t in self.templates:
            idx = [i for i, tt in enumerate(sch.templates) if tt == t]
            out.update(int(i) for i in
                       r.choice(idx, min(k, len(idx)), replace=False))
        return out

    def end_to_end(self) -> dict:
        lat = self.latency_ms
        lag = self.lag_ms[~np.isnan(self.lag_ms)]
        self.notes.update(
            p50_ms=float(np.percentile(lat, 50)),
            max_ms=float(lat.max()),
            gen_lag_p50_ms=float(np.percentile(lag, 50)),
            gen_lag_p99_ms=float(np.percentile(lag, 99)),
            gen_lag_max_ms=float(lag.max()),
            offered_per_s=len(lat) / (self.t1 - self.t0))
        return {"p99_ms": float(np.percentile(lat, 99))}


# ----------------------------------------------------------- closed loop
class StreamLog:
    """One client's record of its queries, in arrays that double when
    full: when it submitted each, saw it resolve and read its count, the
    count, and whether it failed.  The client keeps no Python object per
    query; the queries are drawn again from the stream's seed once the
    window is over (:meth:`ClosedLoop.replay`)."""

    #: each field's type and the value of a query not yet answered
    FIELDS = {"t_sub": (float, np.nan), "t_seen": (float, np.nan),
              "t_read": (float, np.nan), "count": (np.int64, -1),
              "failed": (bool, True)}

    def __init__(self, cap: int = 64):
        self.n = 0
        for name, (dtype, fill) in self.FIELDS.items():
            setattr(self, name, np.full(cap, fill, dtype))

    def add(self, t_sub: float) -> int:
        """Records a submit; returns the query's number in the stream."""
        k = self.n
        if k == len(self.t_sub):
            for name, (dtype, fill) in self.FIELDS.items():
                setattr(self, name, np.concatenate(
                    [getattr(self, name), np.full(k, fill, dtype)]))
        self.t_sub[k] = t_sub
        self.n = k + 1
        return k


class ClosedLoop(FilterTraffic):
    """``streams`` clients in a closed loop against ``BitmapDB.serve()``
    (see module docstring).

    Mix keys: ``streams``, ``templates`` (null: all of the
    configuration's), ``warm_max_q`` (widest one-template bucket warmed),
    ``warm_s`` (untimed streams before the window),
    ``sample_per_template`` (row sets compared per template),
    ``control_per_stream`` (queries of each stream the control answers:
    what one stream got through in a window on the chip),
    ``profile_lead_s``/``profile_s`` (the traced part of a ``--trace 1``
    window)."""

    #: ``phase`` of :meth:`stream`: the window's streams and the warm-up's
    WINDOW, WARM = 0, 1

    def setup(self) -> None:
        self.start_service()
        with self.phase("warm_streams"):
            self.drive_streams(self.WARM, float(self.mix["warm_s"]), keep=0)

    def order(self, phase: int, s: int) -> tuple:
        """Stream ``s``'s generator, and its seeded order of the templates
        (the generator's first draw)."""
        r = sub_rng(self.seed, "queries", phase, s)
        return r, [self.templates[i]
                   for i in r.permutation(len(self.templates))]

    def stream(self, phase: int, s: int):
        """Stream ``s``'s queries, endlessly: ``(template, query,
        priority)``, its :meth:`order` of the templates repeated, each
        query with parameters drawn afresh from the stream's own generator
        (the same seed gives every stream the same sequence, however the
        run interleaves them).  ``priority``, uniform in [0, 1) from a
        generator of its own, picks the sampled row sets: the
        ``sample_per_template`` lowest of each template."""
        r, order = self.order(phase, s)
        pr = sub_rng(self.seed, "sample", phase, s)
        for k in itertools.count():
            t = order[k % len(order)]
            yield t, self.ref.draw(self.cfg, r, t), float(pr.random())

    def drive_streams(self, phase: int, seconds: float, keep: int,
                      on_start=None) -> dict:
        """Run the streams for ``seconds``, one client thread each: a
        client submits its query, waits for it, reads its ``.count``, and
        submits its next, until the window closes.  Its query still
        outstanding then is waited for (up to :data:`LATE_S`), read and
        compared, but not counted as done in the window.  The rows of the
        ``keep`` lowest-priority queries of each template read so far are
        held (a seeded uniform sample of what the window answered).

        Returns each stream's :class:`StreamLog`, the sampled rows and the
        trace ids (keyed by ``(stream, k)``), and the window's times."""
        import jax

        svc = self.svc
        n = int(self.mix["streams"])
        lock = threading.Lock()
        kept: dict = collections.defaultdict(list)  # template -> heap
        rows: dict = {}                # (stream, k) -> rows on the device
        trace_ids: dict = {}           # trace id -> (stream, k); traced only
        logs = [StreamLog() for _ in range(n)]
        errors: list = []
        go = threading.Event()
        t_start = t_end = give_up = 0.0

        def sample(key, t, pri, fut) -> None:
            with lock:
                heap = kept[t]
                if len(heap) < keep or (heap and pri < -heap[0][0]):
                    rows[key] = fut.rows
                    heapq.heappush(heap, (-pri, key))
                    if len(heap) > keep:
                        del rows[heapq.heappop(heap)[1]]

        def client(s: int) -> None:
            try:
                log = logs[s]
                it = self.stream(phase, s)
                go.wait()
                while time.perf_counter() < t_end:
                    t, q, pri = next(it)
                    k = log.add(time.perf_counter())
                    try:
                        # built as a client builds it, when it is sent
                        fut = svc.submit(to_expr(q))
                    except Exception:   # noqa: BLE001 — counted
                        continue
                    if not fut.wait(max(0.0, give_up - time.perf_counter())):
                        return
                    log.t_seen[k] = time.perf_counter()
                    if fut.exception(0) is not None:
                        continue
                    log.count[k] = fut.count      # the client's read
                    log.t_read[k] = time.perf_counter()
                    log.failed[k] = False
                    if fut.trace_id is not None:
                        trace_ids[fut.trace_id] = (s, k)
                    if keep:
                        sample((s, k), t, pri, fut)
            except BaseException as e:          # noqa: BLE001 — re-raised
                errors.append(e)

        threads = [threading.Thread(target=client, args=(s,),
                                    name=f"bench-stream-{s}")
                   for s in range(n)]
        for th in threads:
            th.start()
        t_start = time.perf_counter()
        t_end = t_start + seconds
        give_up = t_end + LATE_S
        go.set()
        if on_start is not None:
            on_start(t_start)
        for th in threads:
            th.join(timeout=max(1.0, give_up - time.perf_counter()) + 30)
            if th.is_alive():
                raise RuntimeError(f"{th.name} did not finish")
        if errors:
            raise errors[0]
        return dict(logs=logs, trace_ids=trace_ids,
                    rows={key: np.asarray(jax.device_get(r))
                          for key, r in rows.items()},
                    t_start=t_start, t_end=t_end,
                    t_close=time.perf_counter())

    @staticmethod
    def flatten(logs: list) -> dict:
        """The streams' records as one record in submission order:
        ``stream`` and ``k`` (its number in the stream) of each query, and
        each :class:`StreamLog` field."""
        stream = np.concatenate([np.full(g.n, s) for s, g in
                                 enumerate(logs)]).astype(np.int64)
        k = np.concatenate([np.arange(g.n) for g in logs]).astype(np.int64)
        cols = {name: np.concatenate([getattr(g, name)[:g.n]
                                      for g in logs])
                for name in StreamLog.FIELDS}
        by_sub = np.argsort(cols["t_sub"], kind="stable")
        return {"stream": stream[by_sub], "k": k[by_sub],
                **{name: a[by_sub] for name, a in cols.items()}}

    def replay(self, phase: int, stream, k) -> tuple:
        """``(templates, queries, priorities)`` of the queries ``(stream,
        k)``, drawn again from each stream's seed as :meth:`stream` drew
        them for the clients."""
        drawn = {int(s): list(itertools.islice(self.stream(phase, int(s)),
                                               int(n)))
                 for s, n in zip(*np.unique(stream, return_counts=True))}
        got = [drawn[int(s)][int(j)] for s, j in zip(stream, k)]
        return ([t for t, _, _ in got], [q for _, q, _ in got],
                [p for _, _, p in got])

    def plan_control(self) -> None:
        """The window the control answers, without the program: each
        stream's first ``control_per_stream`` queries, sampled as a run
        samples."""
        n = int(self.mix["control_per_stream"])
        streams = int(self.mix["streams"])
        stream = np.repeat(np.arange(streams), n)
        tmpl, qs, pri = self.replay(self.WINDOW, stream,
                                    np.tile(np.arange(n), streams))
        k = int(self.mix["sample_per_template"])
        by_t: dict = collections.defaultdict(list)
        for j, (t, p) in enumerate(zip(tmpl, pri)):
            by_t[t].append((p, j))
        self.sample = {j for cand in by_t.values()
                       for _, j in sorted(cand)[:k]}
        self.window = Schedule(np.zeros(len(qs)), qs, tmpl, np.zeros(0))

    def measure(self, seconds: float) -> None:
        from repro.obs import metrics

        def engine():
            # waves served and bucket executors called, program-wide
            c = dict(metrics.GLOBAL.collect())
            return (c["engine_waves_total"].value,
                    c["engine_bucket_dispatches_total"].value)

        e0 = engine()
        w = self.drive_streams(self.WINDOW, seconds,
                               keep=int(self.mix["sample_per_template"]),
                               on_start=self.run.window_started)
        waves, dispatches = (b - a for a, b in zip(e0, engine()))
        self.notes["dispatches_per_wave"] = dispatches / max(waves, 1)
        self.record = f = self.flatten(w["logs"])
        j_of = {(int(s), int(k)): j
                for j, (s, k) in enumerate(zip(f["stream"], f["k"]))}
        rows = {j_of[key]: r for key, r in w["rows"].items()}
        self.sample = set(rows)
        self.served = Served(w["t_start"], f["t_sub"], f["t_seen"],
                             f["count"], f["failed"], rows,
                             {tid: j_of[key]
                              for tid, key in w["trace_ids"].items()},
                             w["t_close"])
        self.t0, self.t1 = w["t_start"], w["t_end"]
        self.notes["rows_compared"] = len(self.sample)
        self.done_in_window = int(np.count_nonzero(
            ~f["failed"] & (f["t_read"] <= w["t_end"])))
        self.latency_ms = (f["t_read"] - f["t_sub"]) * 1e3
        # from a query's resolution to its stream's next submit
        self.resubmit_ms = np.concatenate([
            (g.t_sub[1:g.n] - g.t_seen[:g.n - 1]) * 1e3
            for g in w["logs"]])

    @functools.cached_property
    def window(self) -> Schedule:
        """The queries the window submitted, in submission order, drawn
        again from their streams' seeds (set by :meth:`plan_control` for
        the control)."""
        f = self.record
        tmpl, qs, _ = self.replay(self.WINDOW, f["stream"], f["k"])
        return Schedule(f["t_sub"] - self.t0, qs, tmpl, np.zeros(0))

    def end_to_end(self) -> dict:
        lat = self.latency_ms[~np.isnan(self.latency_ms)]
        lag = self.resubmit_ms[~np.isnan(self.resubmit_ms)]
        self.notes.update(
            queries=len(self.record["t_sub"]),
            done_in_window=self.done_in_window,
            rtt_p50_ms=float(np.percentile(lat, 50)) if lat.size else None,
            rtt_p99_ms=float(np.percentile(lat, 99)) if lat.size else None,
            resubmit_p50_ms=float(np.percentile(lag, 50)) if lag.size
            else None,
            resubmit_p99_ms=float(np.percentile(lag, 99)) if lag.size
            else None)
        return {"qps": self.done_in_window / (self.t1 - self.t0)}


# ------------------------------------------------------------------ load
class Load(Traffic):
    """Index creation from a host pool of records (see module docstring).

    Mix keys: ``profile_lead_s``/``profile_s``, ``sample_blocks`` (blocks
    of each session whose rows are compared)."""

    def make_data(self) -> None:
        """The host pool of records (set-up phase ``data``)."""
        cfg = self.cfg
        self.block = cfg["block_records"]
        self.blocks_per_session = cfg["session_records"] // self.block
        with self.phase("data"):
            self.pool = self.ref.generate_pool(cfg, self.seed)
        self.pool_blocks = self.pool.shape[0] // self.block

    def plan_control(self, sessions: int) -> None:
        """The blocks a window of ``sessions`` whole sessions compares,
        without the program: the control's answers take their place."""
        r = reference.rng(self.seed, "sessions")
        k = int(self.mix["sample_blocks"])
        self.count_errors = 0
        self.samples = [(int(b), None) for _ in range(sessions)
                        for b in sorted(r.choice(self.blocks_per_session, k,
                                                 replace=False))]

    def setup(self) -> None:
        import jax

        self.make_data()
        width = self.block // 32

        @jax.jit
        def block_rows(buf, start):
            return jax.lax.dynamic_slice_in_dim(buf, start, width, axis=1)

        self._block_rows = block_rows
        with self.phase("warmup"):
            # one whole session, as the window runs them: every append
            # re-slices the live index at a new width, one program each
            db = self.new_session()
            for b in range(self.blocks_per_session):
                db.append_encoded(self.pool_block(b))
            jax.block_until_ready(
                self._block_rows(db.indexer.view()[0], 0))
            del db
            gc.collect()

    def pool_block(self, b: int) -> np.ndarray:
        s = (b % self.pool_blocks) * self.block
        return self.pool[s:s + self.block]

    def new_session(self):
        import repro
        return repro.BitmapDB(
            num_keys=self.cfg["num_keys"], backend="auto",
            capacity_words=self.cfg["session_records"] // 32
            + self.block // 32 + 1)

    def measure(self, seconds: float) -> None:
        import jax

        r = reference.rng(self.seed, "sessions")
        k = int(self.mix["sample_blocks"])
        self.samples = []              # (pool block, rows read back)
        self.count_errors = 0
        records = blocks = sessions = 0

        def close(db, appended):
            # read back the sampled blocks of a session before dropping it
            nonlocal sessions
            sessions += 1
            pick = r.choice(appended, min(k, appended), replace=False)
            with self.run.span("bench.readback"):
                buf = db.indexer.view()[0]
                for b in sorted(int(x) for x in pick):
                    self.samples.append((b, np.asarray(jax.device_get(
                        self._block_rows(buf, b * (self.block // 32))))))
            if db.num_records != appended * self.block:
                self.count_errors += 1

        block_s = []                   # host seconds of each append
        t_start = time.perf_counter()
        self.run.window_started(t_start)
        t_end = t_start + seconds
        db, j = self.new_session(), 0
        while (t_b := time.perf_counter()) < t_end:
            with self.run.span("bench.append"):
                db.append_encoded(self.pool_block(j))
            block_s.append(time.perf_counter() - t_b)
            j += 1
            records += self.block
            blocks += 1
            if j == self.blocks_per_session:
                close(db, j)
                with self.run.span("bench.session"):
                    del db
                    db, j = self.new_session(), 0
        jax.block_until_ready(db.indexer.view()[0])
        t_done = time.perf_counter()
        if j:
            close(db, j)
        self.db = db
        self.t0, self.t1 = t_start, t_done
        self.records, self.blocks_done = records, blocks
        self.append_ms = ms = np.asarray(block_s) * 1e3
        self.notes.update(sessions=sessions, blocks=blocks,
                          block_ms_p50=float(np.median(ms)),
                          block_ms_p99=float(np.percentile(ms, 99)),
                          block_ms_max=float(ms.max()),
                          blocks_slow=int((ms > 2 * np.median(ms)).sum()))

    def release(self) -> None:
        del self.db
        gc.collect()

    @property
    def attempted(self) -> int:
        return self.blocks_done

    @property
    def failed(self) -> int:
        return 0

    def end_to_end(self) -> dict:
        return {"ingest_rec_s": self.records / (self.t1 - self.t0)}

    def check(self, control: bool = False) -> list:
        bits = 0
        for b, got in self.samples:
            recs = self.pool_block(b)
            if control:
                got = self.ref.control_rows(self.cfg, recs)
            want = self.ref.index_rows(self.cfg, recs)
            bits += int(np.bitwise_count(got ^ want).sum())
        return [Check("sampled_blocks_missing",
                      float(len(self.samples) == 0), 0),
                Check("bit_mismatches", float(bits), 0),
                Check("record_count_errors", float(self.count_errors), 0)]

    def create_bytes(self, records: int) -> float:
        """Bytes index creation must move at least: the records read once
        and the key rows written once."""
        w = self.cfg["words_per_record"]
        return float(records * w * 4 + self.cfg["num_keys"] * records / 8)


GENERATORS = {"open_loop": OpenLoop, "closed_loop": ClosedLoop,
              "load": Load}


def generator_for(mix: dict):
    try:
        return GENERATORS[mix["generator"]]
    except KeyError:
        raise KeyError(f"mix names generator {mix.get('generator')!r}; have: "
                       f"{sorted(GENERATORS)}") from None
