"""Measured cost model: ``auto`` backend dispatch as a calibrated decision.

Bulk-bitwise filtering is bandwidth-bound, so the right backend for a
query wave is a *measured* property of the host, not a static preference.
This module owns that measurement and the per-wave decision:

  * :class:`Calibration` — per-backend roofline coefficients (sustained
    streamed words/sec on the fused-pass path + fixed per-dispatch
    overhead) plus the host's STREAM-class copy bandwidth.  Measured by
    :func:`measure_calibration` (what ``benchmarks/roofline.py bitmap``
    and the ``engine_backend_sweep`` bench run), persisted as JSON by
    :func:`save_calibration`, and loaded lazily by :func:`get_calibration`
    (path: ``$REPRO_BITMAP_CALIBRATION`` or
    ``results/bitmap_calibration.json``; conservative per-platform
    defaults apply until a measurement exists, and a file measured on
    another platform is refused).
  * :func:`decide` — given the wave's lowered plans, the packed word
    count, the segment count, and optional :class:`~repro.engine.planner.
    KeyStats`, estimate each candidate backend's wall time

        t(b) = dispatches x overhead(b) + streamed_words / words_per_sec(b)

    over the canonically *padded* bucket shapes (what actually executes),
    and pick the cheapest — together with whether common-clause factoring
    shrinks the streamed words (pass-fusion depth) and whether a uniform
    segment chain should stack into one vmapped dispatch per bucket
    (stacking buys ``(S - 1) x dispatches`` overheads for one extra
    stack-copy of the chain at copy bandwidth).  Selectivity estimates
    enter as the expected result-materialization term and are surfaced in
    the decision's ``terms`` (and through ``BitmapDB.explain``).

Decisions never change a result bit — every candidate is bit-identical
(the differential sweep gates that); the model only chooses which
executor cache key a wave lands on, so a mid-traffic switch costs nothing
once :meth:`repro.serve.service.BitmapService.warmup` has pre-compiled
the candidates.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Iterable, Mapping, Sequence

import jax

from repro.engine import backends, planner
from repro.obs import metrics as _obs_metrics

# cost-model observability: calls vs computed = memo hit rate (the
# decision memo is process-global, so its meters are too)
_DECIDE_CALLS = _obs_metrics.GLOBAL.counter(
    "costmodel_decide_calls_total", "auto-dispatch decisions requested")
_DECIDE_COMPUTED = _obs_metrics.GLOBAL.counter(
    "costmodel_decisions_computed_total",
    "decisions actually derived (memo misses + uncacheable)")

ENV_PATH = "REPRO_BITMAP_CALIBRATION"
DEFAULT_PATH = os.path.join("results", "bitmap_calibration.json")
CALIBRATION_VERSION = 1

#: Candidates are backends within this factor of the fastest calibrated
#: words/sec — a backend three orders of magnitude off (the interpreted
#: Pallas path on CPU) is never worth warming or considering.
CANDIDATE_CUTOFF = 32.0


@dataclasses.dataclass(frozen=True)
class BackendProfile:
    """Roofline coefficients of one backend on this host."""
    words_per_sec: float          # sustained streamed uint32 words/sec
    dispatch_overhead_s: float    # fixed cost per compiled-executor call


@dataclasses.dataclass(frozen=True)
class Calibration:
    """One host's measured (or default) bitmap-path roofline."""
    profiles: tuple[tuple[str, BackendProfile], ...]
    copy_bytes_per_sec: float     # STREAM-class copy bandwidth (r+w bytes)
    platform: str                 # jax.default_backend() at measurement
    source: str = "default"       # "default" | "measured"

    def profile(self, name: str) -> BackendProfile | None:
        for n, p in self.profiles:
            if n == name:
                return p
        return None

    def to_json(self) -> str:
        return json.dumps({
            "version": CALIBRATION_VERSION,
            "platform": self.platform,
            "source": self.source,
            "copy_bytes_per_sec": self.copy_bytes_per_sec,
            "backends": {n: {"words_per_sec": p.words_per_sec,
                             "dispatch_overhead_s": p.dispatch_overhead_s}
                         for n, p in self.profiles},
        }, indent=2, sort_keys=True) + "\n"

    @classmethod
    def from_json(cls, text: str) -> "Calibration":
        d = json.loads(text)
        if d.get("version") != CALIBRATION_VERSION:
            raise ValueError(f"calibration version {d.get('version')!r} "
                             f"!= {CALIBRATION_VERSION}")
        profs = tuple(sorted(
            (n, BackendProfile(float(p["words_per_sec"]),
                               float(p["dispatch_overhead_s"])))
            for n, p in d["backends"].items()))
        return cls(profs, float(d["copy_bytes_per_sec"]),
                   str(d.get("platform", "cpu")),
                   str(d.get("source", "measured")))


# Uninformed priors, used only until a measurement exists.  The shapes of
# these numbers matter more than their values: on CPU the interpreted
# Pallas path is orders of magnitude off (never a candidate), the bulk
# sweep beats the per-pass path on big rows but pays slightly more fixed
# setup; on TPU the compiled kernels lead.
_DEFAULTS = {
    "cpu": (
        ("bulk", BackendProfile(3.0e9, 6e-5)),
        ("pallas", BackendProfile(5.0e5, 2e-3)),
        ("ref", BackendProfile(2.0e9, 4e-5)),
    ),
    "tpu": (
        ("bulk", BackendProfile(1.8e11, 4e-5)),
        ("pallas", BackendProfile(1.5e11, 3e-5)),
        ("ref", BackendProfile(1.0e11, 3e-5)),
    ),
}
_DEFAULT_COPY = {"cpu": 1.0e10, "tpu": 8.19e11}


def _platform_default() -> Calibration:
    """The priors for the running platform.  A platform without priors is
    an error: borrowing another platform's numbers would rank backends by
    a device that is not there."""
    plat = jax.default_backend()
    if plat not in _DEFAULTS:
        raise RuntimeError(
            f"no cost-model priors for platform {plat!r} (have "
            f"{sorted(_DEFAULTS)}); measure one with "
            "`python benchmarks/roofline.py bitmap`")
    return Calibration(_DEFAULTS[plat], _DEFAULT_COPY[plat], plat, "default")


def calibration_path() -> str:
    return os.environ.get(ENV_PATH, DEFAULT_PATH)


_active: Calibration | None = None


def get_calibration() -> Calibration:
    """The process-wide calibration: an explicit :func:`set_calibration`
    override, else the persisted measurement at :func:`calibration_path`,
    else the per-platform defaults.  A persisted file that does not parse,
    or that was measured on another platform than the running one, raises:
    it is never silently used or replaced."""
    global _active
    if _active is None:
        path = calibration_path()
        if os.path.exists(path):
            cal = load_calibration(path)
            plat = jax.default_backend()
            if cal.platform != plat:
                raise RuntimeError(
                    f"calibration {path} was measured on {cal.platform!r}, "
                    f"but this process runs on {plat!r}; remove it or "
                    f"point ${ENV_PATH} at a {plat} measurement")
            _active = cal
        else:
            _active = _platform_default()
    return _active


def set_calibration(cal: Calibration | None) -> None:
    """Install (or with ``None`` reset) the active calibration."""
    global _active
    _active = cal


def load_calibration(path: str) -> Calibration:
    with open(path) as f:
        return Calibration.from_json(f.read())


def save_calibration(cal: Calibration, path: str | None = None) -> str:
    """Persist a calibration as JSON (atomic tmp+replace); returns the
    path written."""
    path = path or calibration_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(cal.to_json())
    os.replace(tmp, path)
    return path


def candidates(cal: Calibration | None = None) -> tuple[str, ...]:
    """Backends worth considering (and pre-warming) on this host:
    registered, calibrated, and within :data:`CANDIDATE_CUTOFF` of the
    fastest calibrated words/sec."""
    cal = cal or get_calibration()
    regs = set(backends.available_backends()) - {"auto"}
    profs = [(n, p) for n, p in cal.profiles if n in regs]
    if not profs:
        return (backends.resolve_backend("auto"),)
    best = max(p.words_per_sec for _, p in profs)
    out = tuple(sorted(n for n, p in profs
                       if p.words_per_sec * CANDIDATE_CUTOFF >= best))
    return out or (backends.resolve_backend("auto"),)


# ------------------------------------------------------------------ decision
@dataclasses.dataclass(frozen=True)
class Decision:
    """One wave's cost-model choice (never affects result bits)."""
    backend: str
    factor: bool                  # apply common-clause factoring first
    stack_uniform: bool           # stack a uniform segment chain
    estimates: tuple[tuple[str, float], ...]   # per-candidate seconds
    terms: Mapping[str, float]    # the model's inputs, for explain()

    @property
    def est_seconds(self) -> float:
        return dict(self.estimates)[self.backend]


def _bucket_shapes(plans: Sequence) -> tuple[dict, int, int]:
    """Canonical padded bucket histogram of a wave: {(g, p, l): count},
    plus composite-fallback and contradiction counts.  Uses the batch
    layer's lowering cache, so a steady-state wave costs dict probes."""
    from repro.engine import batch  # deferred: batch imports this module
    shapes: dict[tuple[int, int, int], int] = {}
    composite = zeros = 0
    for pl in plans:
        if isinstance(pl, planner.CompositePlan):
            composite += 1
            continue
        _, shape, _, _ = batch._lowered(pl)
        if shape is None:
            zeros += 1
        else:
            shapes[shape] = shapes.get(shape, 0) + 1
    return shapes, composite, zeros


def _pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _streamed_words(shapes: dict, nw: int) -> float:
    """Words the padded bucket dispatches move: every literal slot reads
    ``nw`` operand words per query of the (pow2-padded) bucket, plus one
    result-row write per query."""
    return float(sum(_pow2(q) * (g * p * l + 1) * nw
                     for (g, p, l), q in shapes.items()))


def _maybe_factored(plans: Sequence) -> list | None:
    """Factored twins of a wave's plans, or None when no plan has more
    than one clause (factoring can't help)."""
    if not any(isinstance(pl, planner.QueryPlan) and len(pl.clauses) > 1
               for pl in plans):
        return None
    return [planner.factor(pl)
            if isinstance(pl, planner.QueryPlan) and pl.clauses else pl
            for pl in plans]


def estimate_matches(plans: Sequence, stats: planner.KeyStats | None
                     ) -> float | None:
    """Expected matching records across a wave (union bound per plan):
    the result-materialization term, and what ``explain`` reports."""
    if stats is None:
        return None
    total = 0.0
    for pl in plans:
        if isinstance(pl, planner.QueryPlan):
            est = sum(stats.clause_estimate(c) for c in pl.clauses)
        elif isinstance(pl, planner.FactoredPlan):
            est = sum(stats.clause_estimate(c) if c else stats.num_records
                      for c, _ in pl.groups)
        else:                     # composite: no cheap bound
            est = stats.num_records
        total += min(float(est), float(stats.num_records))
    return total


def decide(plans: Sequence, *, num_words: int, num_segments: int = 1,
           num_keys: int | None = None,
           stats: planner.KeyStats | None = None,
           cal: Calibration | None = None,
           allow_factor: bool = True) -> Decision:
    """Choose (backend, factoring, segment stacking) for one wave of
    lowered plans over an index of ``num_words`` packed words per segment
    (``num_segments`` uniform segments).  Pure host arithmetic — no
    device work; the heavy inputs come from the batch layer's caches, and
    the whole decision memoizes on the wave's plan tuple: a steady-state
    serving loop re-submitting the same plans pays one cache probe, not
    a re-derivation (a re-registered backend set or new calibration is
    part of the key, so neither ever serves a stale choice)."""
    _DECIDE_CALLS.inc()
    cal = cal or get_calibration()
    try:
        return _decide_cached(tuple(plans), num_words, num_segments,
                              num_keys, stats, cal, allow_factor,
                              backends.available_backends())
    except TypeError:            # unhashable plan object: decide uncached
        return _decide_impl(plans, num_words, num_segments, num_keys,
                            stats, cal, allow_factor)


@functools.lru_cache(maxsize=512)
def _decide_cached(plans, num_words, num_segments, num_keys, stats, cal,
                   allow_factor, _registered):
    return _decide_impl(plans, num_words, num_segments, num_keys, stats,
                        cal, allow_factor)


def _decide_impl(plans, num_words, num_segments, num_keys, stats, cal,
                 allow_factor) -> Decision:
    _DECIDE_COMPUTED.inc()
    cands = candidates(cal)
    shapes, composite, zeros = _bucket_shapes(plans)
    words_plain = _streamed_words(shapes, num_words)

    factored = _maybe_factored(plans) if allow_factor else None
    use_factor = False
    shapes_used = shapes
    words = words_plain
    if factored is not None:
        shapes_f, _, _ = _bucket_shapes(factored)
        words_f = _streamed_words(shapes_f, num_words)
        # factoring trades fewer streamed words for (usually) deeper
        # 2-pass buckets; adopt it only on a real word reduction
        if words_f < words_plain * 0.95:
            use_factor = True
            shapes_used = shapes_f
            words = words_f

    n_buckets = max(len(shapes_used), 1) if shapes_used else 0
    n_buckets += composite            # composites dispatch out-of-band
    s = max(int(num_segments), 1)
    total_words = words * s
    # stacking a uniform chain: one stack-copy of the whole chain
    # (S x M x Nw words read + written) buys (S-1) x buckets dispatches
    stack_bytes = 0.0
    if s > 1 and num_keys is not None:
        stack_bytes = 2.0 * s * num_keys * num_words * 4.0

    est: list[tuple[str, float]] = []
    est_stacked: dict[str, float] = {}
    for name in cands:
        prof = cal.profile(name)
        if prof is None:
            continue
        t_work = total_words / max(prof.words_per_sec, 1.0)
        t_flat = n_buckets * s * prof.dispatch_overhead_s + t_work
        if s > 1:
            t_stk = (n_buckets * prof.dispatch_overhead_s + t_work
                     + stack_bytes / max(cal.copy_bytes_per_sec, 1.0))
            est_stacked[name] = t_stk
            est.append((name, min(t_flat, t_stk)))
        else:
            est.append((name, t_flat))
    if not est:                       # calibration names nothing usable
        name = backends.resolve_backend("auto")
        return Decision(name, False, True, ((name, 0.0),),
                        {"streamed_words": total_words})
    best, t_best = min(est, key=lambda kv: (kv[1], kv[0]))
    stack = s > 1 and est_stacked.get(best, float("inf")) <= t_best + 1e-12

    terms: dict[str, float] = {
        "streamed_words": total_words,
        "streamed_bytes": total_words * 4.0,
        "buckets": float(n_buckets),
        "segments": float(s),
        "queries": float(len(plans)),
        "contradictions": float(zeros),
        "composites": float(composite),
        "words_plain": words_plain * s,
        "copy_bytes_per_sec": cal.copy_bytes_per_sec,
    }
    em = estimate_matches(plans, stats)
    if em is not None:
        terms["est_matches"] = em
        terms["est_selectivity"] = (em / (len(plans) * stats.num_records)
                                    if plans and stats.num_records else 0.0)
    return Decision(best, use_factor, stack, tuple(est), terms)


# -------------------------------------------------------------- measurement
def measure_calibration(*, num_records: int = 1 << 20, num_keys: int = 256,
                        num_queries: int = 64, reps: int = 3,
                        backend_names: Iterable[str] | None = None,
                        probe_seconds: float = 0.5,
                        seed: int = 0) -> Calibration:
    """Measure this host's bitmap-path roofline: STREAM-class copy
    bandwidth plus, per backend, sustained streamed words/sec on a
    representative fused-pass bucket and the fixed per-dispatch overhead.

    Backends whose small probe already exceeds ``probe_seconds`` (the
    interpreted Pallas path on CPU) keep the probe-sized estimate instead
    of paying a full-size run.  Import-time free; runs device work.
    """
    import time

    import jax.numpy as jnp
    import numpy as np

    from repro.engine import batch
    from repro.engine.planner import QueryPlan

    rng = np.random.default_rng(seed)
    nw = max(num_records // 32, 1)
    packed = jnp.asarray(
        rng.integers(0, 2 ** 32, (num_keys, nw), dtype=np.uint32))

    def timed(fn, r=reps):
        jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(r):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    # STREAM-class copy: one read + one write of the whole index
    copy = jax.jit(lambda a: a | jnp.uint32(0))
    t_copy = timed(lambda: copy(packed))
    copy_bps = 2.0 * packed.nbytes / t_copy

    def two_lit_plans(m):
        return [QueryPlan((((int(rng.integers(0, m)), False),
                            (int(rng.integers(0, m)), True)),))
                for _ in range(num_queries)]

    names = tuple(backend_names) if backend_names is not None else tuple(
        sorted(set(backends.available_backends()) - {"auto"}))
    small_nw = 2048
    small = packed[:, :small_nw]
    tiny = packed[:, :16]
    profiles = []
    for name in names:
        plans = two_lit_plans(num_keys)
        words_small = _streamed_words({(1, 1, 2): num_queries}, small_nw)
        t_small = timed(lambda: batch.execute_many(
            small, plans, num_records=small_nw * 32, backend=name), r=1)
        if t_small > probe_seconds:
            wps = words_small / t_small
            t_tiny = t_small * 16 / small_nw  # don't re-run a slow path
        else:
            words = _streamed_words({(1, 1, 2): num_queries}, nw)
            t_full = timed(lambda: batch.execute_many(
                packed, plans, num_records=num_records, backend=name))
            wps = words / t_full
            t_tiny = timed(lambda: batch.execute_many(
                tiny, plans[:1], num_records=16 * 32, backend=name))
        profiles.append((name, BackendProfile(wps, max(t_tiny, 1e-7))))
    return Calibration(tuple(sorted(profiles)), copy_bps,
                       jax.default_backend(), "measured")
