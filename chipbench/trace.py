"""Reduction of one profiler trace to the numbers the layer readers use.

The JAX profiler writes an ``.xplane.pb``.  Its device planes
(``/device:TPU:<i>``) carry one line of XLA modules (one event per
dispatch of a jitted program) and one line of XLA ops (the device
operations inside them); the host plane carries the harness's
``TraceAnnotation`` spans on the same clock.  Times here are seconds.

* busy: the union of a device's op intervals inside the traced window,
  averaged over the devices;
* module time: the union of the intervals of the modules whose names
  match a kernel's pattern;
* idle gaps: the stretches of the window in which device 0 runs no op,
  each put down to the innermost harness span open at its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(merged: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi) that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    """What the reduction needs of one trace: the window, the harness
    spans, and per device its op and module events."""
    window: Interval
    spans: List[Event]
    ops: Dict[str, List[Event]]         # device plane -> op events
    modules: Dict[str, List[Event]]     # device plane -> module events

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def devices(self) -> List[str]:
        return sorted(self.ops)

    def busy(self, device: str) -> List[Interval]:
        return clip(union((e.start, e.end) for e in self.ops[device]),
                    *self.window)

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(length(self.busy(d)) for d in devs) / len(devs)

    def module_s(self, patterns: Sequence[str],
                 device: Optional[str] = None) -> float:
        """Seconds inside the window in which a module matching one of
        ``patterns`` (regular expressions, searched) ran, on ``device``
        or averaged over the devices."""
        rx = [re.compile(p) for p in patterns]
        devs = [device] if device else self.devices()
        tot = 0.0
        for d in devs:
            iv = [(e.start, e.end) for e in self.modules.get(d, ())
                  if any(r.search(e.name) for r in rx)]
            tot += length(clip(union(iv), *self.window))
        return tot / max(len(devs), 1)

    def op_s(self, patterns: Sequence[str],
             device: Optional[str] = None) -> float:
        """Like ``module_s`` for op events (collectives, for instance)."""
        rx = [re.compile(p) for p in patterns]
        devs = [device] if device else self.devices()
        tot = 0.0
        for d in devs:
            iv = [(e.start, e.end) for e in self.ops.get(d, ())
                  if any(r.search(e.name) for r in rx)]
            tot += length(clip(union(iv), *self.window))
        return tot / max(len(devs), 1)

    def top_ops(self, k: int = 10) -> List[List]:
        """Device 0's ops by self seconds inside the window (an op's time
        less that of the ops nested in it), each named by its module and
        its HLO name."""
        devs = self.devices()
        if not devs:
            return []
        tot: Dict[str, float] = {}
        for name, secs in self_times(self.ops[devs[0]],
                                     self.modules.get(devs[0], []),
                                     *self.window):
            tot[name] = tot.get(name, 0.0) + secs
        return [[n, v] for n, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def span_at(self, t: float) -> str:
        """The innermost harness span open at ``t``."""
        best = None
        for sp in self.spans:
            if sp.name == WINDOW_SPAN or not sp.start <= t < sp.end:
                continue
            if best is None or sp.end - sp.start < best.end - best.start:
                best = sp
        return best.name if best is not None else "(no span)"

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Device 0's idle seconds in the window, summed by the harness
        span open in each gap, largest first."""
        devs = self.devices()
        if not devs:
            return []
        by: Dict[str, float] = {}
        for s, e in gaps(self.busy(devs[0]), *self.window):
            name = self.span_at(0.5 * (s + e))
            by[name] = by.get(name, 0.0) + (e - s)
        return [[n, v] for n, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def span_s(self, names: Sequence[str]) -> float:
        """Summed seconds of the harness spans named ``names``."""
        return sum(sp.end - sp.start for sp in self.spans
                   if sp.name in names)


def self_times(ops: Sequence[Event], modules: Sequence[Event], lo: float,
               hi: float) -> List[Tuple[str, float]]:
    """(name, self seconds inside [lo, hi)) of each op: its clipped time
    less the clipped time of the ops nested in it on the same line.  The
    name is ``<module>/<op>``, the op cut to its HLO name."""
    mods = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in mods]
    out: List[List] = []
    stack: List[int] = []
    for ev in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and not (ev.start < out[stack[-1]][2]
                             and ev.end <= out[stack[-1]][2]):
            stack.pop()
        i = bisect.bisect_right(starts, ev.start) - 1
        mod = mods[i].name.split("(")[0] if i >= 0 and \
            mods[i].end >= ev.start else "?"
        secs = max(0.0, min(ev.end, hi) - max(ev.start, lo))
        if stack:
            out[stack[-1]][1] -= secs
        out.append([f"{mod}/{ev.name.split(' = ')[0]}", secs, ev.end])
        stack.append(len(out) - 1)
    return [(n, s) for n, s, _ in out]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, span_names: Sequence[str]) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Event] = []
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    want = set(span_names) | {WINDOW_SPAN}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    dst = modules.setdefault(plane.name, [])
                else:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dst.append(Event(ev.name, s, s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in want:
                        s = ev.start_ns * 1e-9
                        spans.append(Event(ev.name, s,
                                           s + ev.duration_ns * 1e-9))
    wins = [sp for sp in spans if sp.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    for d in modules:
        ops.setdefault(d, [])
    return Trace((wins[0].start, wins[0].end), spans, ops, modules)
