"""Run one cell of ``BENCHMARK.json`` and print its result line.

The harness is driven by names.  A cell ``<config>.<traffic>`` of
``BENCHMARK.json`` names its configuration (``configs/<config>.json`` and
its plain reference ``configs/<config>.py``) and its traffic mix
(``mixes/<traffic>.json``, whose ``"generator"`` key picks a generator of
:mod:`bench.traffic`).  Each per-layer metric is a reader
``metrics/<name>.py``.  A new configuration, mix or metric is a new file;
nothing here changes.

A run: set up (counted in ``setup_s``), measure for ``--seconds``, read the
device's peak memory, free the program's state, compare with the
reference, print.  ``--trace 1`` is a run of its own: it installs a
``repro.obs`` tracer, takes a profiler trace of part of the window, and
reports the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import faulthandler
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import threading
import time

from bench import peaks, trace_reduce, traffic

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

#: JAX's event for lowering a traced program: once per first-sight jit
#: shape, whether or not the persistent cache then holds its executable
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: JAX's event for compiling (or fetching from the persistent cache) one
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: per-request spans that are open while a request waits, not while a
#: thread works: left out when naming what the host did in a device gap
WAITING_SPANS = ("admission", "queue", "serve")


# ---------------------------------------------------------------- finding
@dataclasses.dataclass
class Config:
    name: str
    sizes: dict
    module: object


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def load_config(name: str, bench: pathlib.Path = BENCH) -> Config:
    with open(bench / "configs" / f"{name}.json") as f:
        sizes = json.load(f)
    mod = _module(bench / "configs" / f"{name}.py", f"bench_config_{name}")
    return Config(name, sizes, mod)


def load_mix(name: str, bench: pathlib.Path = BENCH) -> dict:
    with open(bench / "mixes" / f"{name}.json") as f:
        return json.load(f)


def load_metric(name: str, bench: pathlib.Path = BENCH):
    return _module(bench / "metrics" / f"{name}.py", f"bench_metric_{name}")


def cell_metrics(bm: dict, cell: str, kind: str) -> list:
    """The ``kind`` ("end_to_end" or "per_layer") entries that ``cell``
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in bm[kind]
            if "workloads" not in m or cell in m["workloads"]]


def make_generator(cell_name: str, seed: int, tracer=None, *,
                   root: pathlib.Path = ROOT, sizes: dict | None = None,
                   mix_overrides: dict | None = None):
    """The traffic generator of ``cell_name``, found by name: its
    configuration (with ``sizes`` replacing entries) and its mix (with
    ``mix_overrides``), with a :class:`Run` on ``tracer`` (None: untraced)
    as ``gen.run``; returns ``(BENCHMARK.json, generator)``."""
    bench = root / "bench"
    bm = load_benchmark(root)
    cell = find_cell(bm, cell_name)
    config = load_config(cell["config"], bench)
    config.sizes.update(sizes or {})
    mix = load_mix(cell["traffic"], bench)
    mix.update(mix_overrides or {})
    run = Run(tracer, float(mix["profile_lead_s"]), float(mix["profile_s"]))
    return bm, traffic.generator_for(mix)(config, mix, seed, run)


# ------------------------------------------------------------------- run
class CompileCounter:
    """Counts programs JAX lowers (first-sight jit shapes) while active,
    with their names and the seconds the backend spent compiling."""

    def __init__(self):
        self.count = 0
        self.names: collections.Counter = collections.Counter()
        self.compile_s = 0.0

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == LOWERING_EVENT:
            self.count += 1
            self.names[str(kw.get("fun_name"))] += 1
        elif event == BACKEND_COMPILE_EVENT:
            self.compile_s += duration

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class HostWatch:
    """The host's pauses while active: Python's garbage collections (the
    count and longest of the full ones, the seconds of all) and the times
    a thread woken every :attr:`TICK_S` ran late, by :attr:`PAUSE_S` or
    more (pauses) and by :attr:`STALL_S` or more (stalls).  The first
    :attr:`MAX_DUMPS` stalls dump every thread's stack to standard error
    (``faulthandler``'s own thread writes it, while the stall lasts)."""

    TICK_S = 0.1
    PAUSE_S = 0.05
    STALL_S = 0.5
    MAX_DUMPS = 3

    def __init__(self):
        self.full_gcs = 0
        self.full_gc_max_s = 0.0
        self.gc_s = 0.0
        self.pauses = 0
        self.stalls = 0
        self.late_max_s = 0.0
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch,
                                        name="bench-watch", daemon=True)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        d = time.perf_counter() - self._gc_t0
        self.gc_s += d
        if info["generation"] == 2:
            self.full_gcs += 1
            self.full_gc_max_s = max(self.full_gc_max_s, d)

    def _watch(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.TICK_S):
            now = time.perf_counter()
            late = now - last - self.TICK_S
            last = now
            self.late_max_s = max(self.late_max_s, late)
            self.pauses += late >= self.PAUSE_S
            self.stalls += late >= self.STALL_S
            if self.stalls < self.MAX_DUMPS:
                faulthandler.dump_traceback_later(
                    self.STALL_S + self.TICK_S, file=sys.__stderr__)

    def __enter__(self) -> "HostWatch":
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        faulthandler.cancel_dump_traceback_later()
        gc.callbacks.remove(self._on_gc)

    def notes(self) -> dict:
        return {"gc_full": self.full_gcs,
                "gc_full_max_ms": self.full_gc_max_s * 1e3,
                "gc_ms": self.gc_s * 1e3, "pauses": self.pauses,
                "stalls": self.stalls, "late_max_ms": self.late_max_s * 1e3}


class Run:
    """The hooks a traffic generator calls: ``span`` (a ``repro.obs`` span when the
    run is traced, else nothing) and ``window_started`` (schedules the
    profiler's part of the window)."""

    def __init__(self, tracer, profile_lead_s: float, profile_s: float):
        self.tracer = tracer
        self.lead = profile_lead_s
        self.length = profile_s
        self.sync_pc = None
        self.log_dir = None
        self._thread = None
        self._error: list = []

    def span(self, name: str, **attrs):
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **attrs)

    def window_started(self, t0: float) -> None:
        if self.tracer is None:
            return
        self.log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._thread = threading.Thread(target=self._profile, args=(t0,),
                                        name="bench-profiler")
        self._thread.start()

    def _profile(self, t0: float) -> None:
        import jax
        try:
            d = t0 + self.lead - time.perf_counter()
            if d > 0:
                time.sleep(d)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
                    self.sync_pc = time.perf_counter()
                time.sleep(self.length)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:          # noqa: BLE001 — re-raised
            self._error.append(e)

    def finish_profile(self):
        """Wait for the profiler; returns the reduced trace or None."""
        if self._thread is None:
            return None
        self._thread.join(timeout=300)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        if self._error:
            raise self._error[0]
        try:
            return trace_reduce.reduce(
                trace_reduce.find_xplane(self.log_dir), self.sync_pc)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader reads."""
    gen: object
    window: tuple                 # (t0, t1) on perf_counter
    spans: list                   # repro.obs spans recorded in the run
    counters: dict                # engine counters' change over the window
    compiles: int                 # programs lowered inside the window
    trace: object                 # trace_reduce.Reduced
    peaks: object                 # peaks.Peaks of the device

    def spans_named(self, name: str, inside=None) -> list:
        """Spans called ``name`` that start inside ``inside`` (default: the
        measured window)."""
        a, b = inside if inside is not None else self.window
        return [s for s in self.spans
                if s.name == name and s.t1 is not None and a <= s.t0 < b]


def _counters() -> dict:
    from repro.obs import metrics
    return {name: c.value for name, c in metrics.GLOBAL.collect()
            if isinstance(c, metrics.Counter)}


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: pathlib.Path = ROOT,
             sizes: dict | None = None,
             mix_overrides: dict | None = None) -> dict:
    """Run one cell; returns the result object (see module docstring).
    ``sizes``/``mix_overrides`` replace entries of the configuration and
    the mix (the tests' small rehearsal)."""
    import jax

    from repro import jaxcache
    from repro.obs import trace as obs_trace

    jaxcache.enable()
    bench = root / "bench"
    dev = device_info()
    pk = peaks.peaks(dev["kind"]) if dev["platform"] == "tpu" else None

    tracer = (obs_trace.install(obs_trace.Tracer(capacity=1 << 21))
              if trace else None)
    try:
        bm, gen = make_generator(cell_name, seed, tracer, root=root,
                                 sizes=sizes, mix_overrides=mix_overrides)
        with CompileCounter() as compiles:
            gen.setup()
            # the set-up's heap (data, schedules, JAX's caches of every
            # warmed program) lives as long as the process: keep the
            # window's full collections from scanning it again each time
            gc.collect()
            gc.freeze()
            setup_s = time.perf_counter() - t_process
            gen.notes["gc_frozen"] = gc.get_freeze_count()
            c0, n0 = _counters(), compiles.count
            names0, cs0 = compiles.names.copy(), compiles.compile_s
            with HostWatch() as host:
                gen.measure(seconds)
            c1, n1 = _counters(), compiles.count
            in_window = compiles.names - names0
            compile_s = compiles.compile_s - cs0
        gen.notes.update(host.notes())
        reduced = gen.run.finish_profile()
        spans = tracer.spans() if tracer is not None else []
    finally:
        gc.unfreeze()
        if tracer is not None:
            obs_trace.uninstall(tracer)
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    gen.release()
    t_check = time.perf_counter()
    checks = gen.check()
    check_s = time.perf_counter() - t_check

    out: dict = {"correct": all(c.ok for c in checks),
                 "attempted": gen.attempted, "failed": gen.failed}
    e2e = gen.end_to_end()
    units = {m["name"]: m["unit"] for m in bm["end_to_end"]}
    metrics: dict = {}
    breakdown = None
    if not trace:
        for m in cell_metrics(bm, cell_name, "end_to_end"):
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    else:
        ctx = LayerContext(
            gen=gen, window=(gen.t0, gen.t1),
            spans=spans,
            counters={k: c1.get(k, 0) - c0.get(k, 0) for k in c1},
            compiles=n1 - n0, trace=reduced, peaks=pk)
        for m in cell_metrics(bm, cell_name, "per_layer"):
            v = load_metric(m["name"], bench).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            dev["busy_s"] = reduced.busy_s
            dev["window_s"] = reduced.window_s
            breakdown = {
                "device_ops": trace_reduce.top_ops(reduced),
                "idle_gaps": trace_reduce.label_gaps(
                    reduced, [sp for sp in spans
                              if sp.name not in WAITING_SPANS])}
    out["metrics"] = metrics
    out["device"] = dev
    if breakdown is not None:
        out["breakdown"] = breakdown

    say = lambda s: print(s, flush=True)  # noqa: E731
    say("setup: " + ", ".join(f"{k} {v:.3f}s"
                              for k, v in gen.phases.items())
        + f"; setup_s {setup_s:.3f}s")
    say("window: " + ", ".join(f"{k} {_num(v)}" for k, v in
                               {**e2e, **gen.notes}.items())
        + f"; compiles in window {n1 - n0} ({compile_s:.3f}s compiling: "
        + ", ".join(f"{k} x{v}" for k, v in in_window.most_common(8))
        + f"); peak_bytes_in_use {dev['memory_peak_bytes']}; check "
        f"{check_s:.3f}s")
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def _num(v):
    return f"{v:.6g}" if isinstance(v, float) else str(v)


def print_result(out: dict) -> None:
    """The checks as the last lines of standard error, then the result
    object as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name}: {_num(c['value'])} (limit {_num(c['limit'])})"
              f" {'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(out, default=_json_default), flush=True)


def _json_default(v):
    if hasattr(v, "item"):
        return v.item()
    raise TypeError(f"not JSON serializable: {v!r}")
