"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's own
reader, and gives:

* the device busy time: the union of the intervals in which an operation
  ran on each device (the ``XLA Ops`` line of each ``/device:TPU:<i>``
  plane), averaged over the devices;
* the device self time of each operation (less the operations nested in
  it), by instruction name, and so of each named kernel;
* the device time and count of the executions of named modules (the
  ``XLA Modules`` line);
* a window cut short where the profiler dropped device events;
* the idle gaps between busy intervals, each labelled with the host
  annotation (``bench.*`` spans of the harness, ``serve.*`` / ``jax.*``
  spans of the program, shifted onto the trace's clock) that covers most of
  the gap.

All times are nanoseconds on the trace's clock unless a name says seconds.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# What the TPU profiler writes over a stretch whose device events it lost.
DROPPED = "Trace Buffers Dropped"


@dataclass
class DeviceLine:
    """One device's operations, modules and dropped stretches."""

    starts: np.ndarray          # int64 ns, one per operation
    ends: np.ndarray
    op_ids: np.ndarray          # index into ``names``
    names: list[str]            # an operation's instruction name
    texts: list[str]            # its HLO text
    modules: list[tuple[int, int, str]]
    dropped: list[tuple[int, int]]


@dataclass
class Trace:
    devices: dict[str, DeviceLine]
    host_spans: list[tuple[int, int, str]]  # (start, end, name)


def _short(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def _device(plane) -> DeviceLine:
    starts, ends, ids = [], [], []
    index: dict[str, int] = {}
    names, texts = [], []
    modules, dropped = [], []
    for line in plane.lines:
        if line.name == OPS_LINE:
            for ev in line.events:
                full = ev.name
                i = index.get(full)
                if i is None:
                    i = index[full] = len(names)
                    names.append(_short(full))
                    texts.append(full)
                s = round(ev.start_ns)
                starts.append(s)
                ends.append(s + round(ev.duration_ns))
                ids.append(i)
        elif line.name == MODULES_LINE:
            for ev in line.events:
                s = round(ev.start_ns)
                modules.append((s, s + round(ev.duration_ns), ev.name))
        else:
            for ev in line.events:
                if ev.name == DROPPED:
                    s = round(ev.start_ns)
                    dropped.append((s, s + round(ev.duration_ns)))
    return DeviceLine(np.asarray(starts, np.int64), np.asarray(ends, np.int64),
                      np.asarray(ids, np.int64), names, texts, modules,
                      dropped)


def load(path: str, host_prefixes: tuple[str, ...] = ("bench.",)) -> Trace:
    """Read one ``.xplane.pb``, or the newest under a profiler log dir.
    Its times count from the start of the profiling session, so a float
    holds them to well under a nanosecond."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    from jax.profiler import ProfileData

    devices: dict[str, DeviceLine] = {}
    host: list[tuple[int, int, str]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = _device(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefixes):
                        s = round(ev.start_ns)
                        host.append((s, s + round(ev.duration_ns), ev.name))
    return Trace(devices, host)


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The merged intervals of ``[starts, ends)``, sorted."""
    if not starts.size:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(s.size, dtype=bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def self_times(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Each operation's duration less that of the operations nested in it
    (a ``while`` holds its body's operations on the same line)."""
    order = np.lexsort((-ends, starts))
    own = (ends - starts).astype(np.int64)
    stack: list[int] = []
    s_list, e_list = starts.tolist(), ends.tolist()
    for i in order.tolist():
        while stack and e_list[stack[-1]] <= s_list[i]:
            stack.pop()
        if stack and e_list[i] <= e_list[stack[-1]]:
            own[stack[-1]] -= e_list[i] - s_list[i]
        stack.append(i)
    return own


@dataclass
class Reduction:
    """The numbers the per-layer metrics read."""

    window_s: float                   # the window, less dropped stretches
    busy_s: float                     # union of operations, mean over devices
    op_s: dict[str, float]            # self seconds by instruction name
    op_text: dict[str, str]           # an instruction's HLO text
    module_s: dict[str, float]        # seconds by module name
    module_runs: dict[str, int]       # executions wholly inside the window
    gaps: list[tuple[str, float]]     # (label, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, *patterns: str) -> float:
        """Seconds of the operations whose instruction name starts with
        one of ``patterns`` (a Mosaic call is named after the jitted
        function that makes it)."""
        return sum(t for name, t in self.op_s.items()
                   if name.startswith(patterns))

    def module(self, pattern: str) -> tuple[float, int]:
        """Seconds and executions of the modules whose name holds
        ``pattern``."""
        names = [m for m in self.module_s if pattern in m]
        return (sum(self.module_s[m] for m in names),
                sum(self.module_runs[m] for m in names))


def reduce(trace: Trace, lo: int, hi: int, top: int = 10) -> Reduction:
    """Reduce ``trace`` over ``[lo, hi)``, cut short where the profiler
    dropped device events; ``top`` gaps."""
    if not trace.devices:
        raise ValueError("the trace holds no TPU device plane")
    for dev in trace.devices.values():
        for s, _ in dev.dropped:
            if s < hi:
                hi = max(lo, s)
    window = hi - lo
    busy_total = 0
    op_ns: dict[str, int] = defaultdict(int)
    op_text: dict[str, str] = {}
    mod_ns: dict[str, int] = defaultdict(int)
    mod_runs: dict[str, int] = defaultdict(int)
    gap_list: list[tuple[int, int]] = []
    for dev in trace.devices.values():
        s = np.clip(dev.starts, lo, hi)
        e = np.clip(dev.ends, lo, hi)
        keep = e > s
        s, e, ids = s[keep], e[keep], dev.op_ids[keep]
        us, ue = union(s, e)
        busy_total += int((ue - us).sum())
        bounds = np.concatenate([[lo], np.stack([us, ue], 1).ravel(), [hi]])
        for g0, g1 in bounds.reshape(-1, 2).tolist():
            if g1 > g0:
                gap_list.append((g0, g1))
        own = np.bincount(ids, weights=self_times(s, e),
                          minlength=len(dev.names))
        for i in np.flatnonzero(own).tolist():
            op_ns[dev.names[i]] += int(own[i])
            op_text.setdefault(dev.names[i], dev.texts[i])
        for ms, me, name in dev.modules:
            if lo <= ms and me <= hi:
                mod_ns[name] += me - ms
                mod_runs[name] += 1
    ndev = len(trace.devices)
    gaps = sorted(gap_list, key=lambda g: g[0] - g[1])[:top]
    return Reduction(
        window_s=window / 1e9,
        busy_s=busy_total / ndev / 1e9,
        op_s={k: v / ndev / 1e9 for k, v in op_ns.items()},
        op_text=op_text,
        module_s={k: v / ndev / 1e9 for k, v in mod_ns.items()},
        module_runs={k: v // ndev for k, v in mod_runs.items()},
        gaps=[(label(trace.host_spans, s, e), (e - s) / 1e9)
              for s, e in gaps],
    )


def label(host_spans, s: int, e: int) -> str:
    """The host span that covers most of ``[s, e)``; the innermost
    (shortest) one among equals. ``host.other`` when none does."""
    best, best_key = "host.other", (0, 0)
    for hs, he, name in host_spans:
        cover = min(he, e) - max(hs, s)
        if cover <= 0:
            continue
        key = (cover, -(he - hs))
        if key > best_key:
            best, best_key = name, key
    return best
