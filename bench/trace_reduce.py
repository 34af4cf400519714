"""Reduce a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: the device's busy and idle time over the traced window, device
time per operation and per compiled module, and the longest idle gaps.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip and ``XLA Modules`` one per program
run.  Host and device events share the trace's clock.  The harness marks
one host event, :data:`SYNC`, while it reads ``time.perf_counter()``, so
every time here is returned on the host's ``perf_counter`` clock, the
clock of the program's spans.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: name of the host annotation the harness opens while reading its clock
SYNC = "bench.sync"

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    """One traced window, on the ``perf_counter`` clock (seconds)."""
    t0: float
    t1: float
    devices: int
    busy_s: float                 # union of op intervals, mean per device
    busy: list                    # merged busy intervals of device 0
    ops: dict                     # op name -> device seconds (all devices)
    modules: list                 # (module name, start, end), device 0

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def gaps(self) -> list:
        """Idle intervals of device 0 inside the window, longest first."""
        out, t = [], self.t0
        for s, e in self.busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.t1:
            out.append((t, self.t1))
        return sorted(out, key=lambda g: g[0] - g[1])

    def module_time(self, pattern: str, within=None) -> float:
        """Device seconds of device 0's module runs whose name matches
        ``pattern`` (a regular expression searched in the name), counting
        only runs that start inside one of the intervals ``within``."""
        rx = re.compile(pattern)
        total = 0.0
        for name, s, e in self.modules:
            if not rx.search(name):
                continue
            if within is not None and not any(a <= s < b for a, b in within):
                continue
            total += e - s
        return total


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a profiler session wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def _merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def reduce(path: str, sync_pc: float) -> Reduced:
    """Reduce the trace at ``path``.  ``sync_pc`` is the ``perf_counter``
    reading taken inside the :data:`SYNC` annotation."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    sync_ns = None
    env = {}
    device_planes = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
        elif plane.name == "Task Environment":
            env = dict(plane.stats)
        elif plane.name.startswith("/host:") and sync_ns is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC:
                        sync_ns = ev.start_ns + ev.duration_ns / 2
                        break
    if sync_ns is None:
        raise ValueError(f"{path}: no {SYNC!r} host event to align clocks")
    if not device_planes:
        raise ValueError(f"{path}: no TPU device plane")
    off = sync_pc - sync_ns * 1e-9          # trace ns -> perf_counter s

    def pc(ns):
        return ns * 1e-9 + off

    device_planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    ops: dict = {}
    busy_total = 0.0
    busy0: list = []
    modules0: list = []
    lo = hi = None
    for i, plane in enumerate(device_planes):
        lines = {line.name: line for line in plane.lines}
        ivs = []
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            s, e = pc(ev.start_ns), pc(ev.start_ns + ev.duration_ns)
            ivs.append((s, e))
            ops[ev.name] = ops.get(ev.name, 0.0) + (e - s)
        merged = _merge(ivs)
        busy_total += sum(e - s for s, e in merged)
        if merged:
            lo = merged[0][0] if lo is None else min(lo, merged[0][0])
            hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
        if i == 0:
            busy0 = merged
            for ev in (lines[MODULES_LINE].events
                       if MODULES_LINE in lines else ()):
                modules0.append((ev.name, pc(ev.start_ns),
                                 pc(ev.start_ns + ev.duration_ns)))
    if "profile_start_time" in env and "profile_stop_time" in env:
        span = (int(env["profile_stop_time"])
                - int(env["profile_start_time"])) * 1e-9
        t0 = pc(0)
        t1 = t0 + span
    else:                                   # no session bounds recorded
        t0, t1 = lo, hi
    if lo is not None:
        t0, t1 = min(t0, lo), max(t1, hi)
    return Reduced(t0=t0, t1=t1, devices=len(device_planes),
                   busy_s=busy_total / len(device_planes), busy=busy0,
                   ops=ops, modules=modules0)


def label_gaps(red: Reduced, spans: list, top: int = 10) -> list:
    """The ``top`` longest idle gaps of the device, each named by the
    innermost host span open at the gap's midpoint (``"no span"`` when
    none was): ``[[label, seconds], ...]``."""
    out = []
    for s, e in red.gaps()[:top]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans
                 if sp.t1 is not None and sp.t0 <= mid < sp.t1]
        label = (max(open_, key=lambda sp: sp.t0).name if open_
                 else "no span")
        out.append([label, e - s])
    return out


_HLO = re.compile(r"^(%?[\w.-]+) = (\w+)\[([\d,]*)\][^ ]* ([\w-]+)\(")


def op_label(text: str) -> str:
    """A short name for an op event whose name is its HLO text:
    ``%cam_match.1 custom-call u32[262144,8]``."""
    m = _HLO.match(text)
    if m is None:
        return text[:80]
    name, dtype, shape, kind = m.groups()
    return f"{name} {kind} {dtype}[{shape}]"


def top_ops(red: Reduced, top: int = 10) -> list:
    """``[[op, device seconds], ...]``, the ``top`` largest, ops named by
    :func:`op_label` (events of one label summed)."""
    acc: dict = {}
    for name, secs in red.ops.items():
        label = op_label(name)
        acc[label] = acc.get(label, 0.0) + secs
    return [[name, secs] for name, secs in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
