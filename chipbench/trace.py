"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

The benchmark wraps its traced window in a host annotation named
``chipbench.window`` and each call into a measured layer in an annotation
``chipbench.<layer>:<count>``.  From the trace this module reads:

* per device plane (``/device:TPU:<i>``), the intervals of its ``XLA Ops``
  line (an op inside a loop nests inside the loop's op): busy time is their
  union inside the window, idle is the rest;
* per op name, its summed device time (the ``device_ops`` breakdown), and
  the device time of ops whose name holds a given pattern (a kernel);
* the host annotations, on the same clock, so device time can be split by
  what the host was calling when it ran, and idle gaps named by it.

Events are read once into arrays; a window of a busy device holds millions.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np
from jax.profiler import ProfileData

WINDOW = "chipbench.window"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> List[Interval]:
    """Disjoint, sorted union of (start, end) intervals."""
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(a):
        return []
    a = a[np.argsort(a[:, 0], kind="stable")]
    reach = np.maximum.accumulate(a[:, 1])
    new = np.ones(len(a), bool)
    new[1:] = a[1:, 0] > reach[:-1]
    starts = a[new, 0]
    last = np.flatnonzero(new)[1:] - 1
    ends = np.append(reach[last], reach[-1])
    return list(zip(starts.tolist(), ends.tolist()))


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class _Ops:
    """One device's op events as arrays: start, end (ns) and name id."""

    def __init__(self, events):
        names: Dict[str, int] = {}
        start, end, ids = [], [], []
        for e in events:
            start.append(e.start_ns)
            end.append(e.end_ns)
            ids.append(names.setdefault(e.name, len(names)))
        self.start = np.asarray(start, np.float64)
        self.end = np.asarray(end, np.float64)
        self.ids = np.asarray(ids, np.int64)
        self.names = list(names)

    def inside(self, lo, hi):
        return (self.end > lo) & (self.start < hi)

    def clipped(self, lo, hi):
        return np.minimum(self.end, hi) - np.maximum(self.start, lo)

    def matching(self, pattern: str) -> np.ndarray:
        rx = re.compile(pattern)
        hit = np.asarray([bool(rx.search(n)) for n in self.names], bool)
        return hit[self.ids] if len(self.ids) else np.zeros(0, bool)


class Trace:
    """Device ops and host annotations of one traced window (ns)."""

    def __init__(self, path: str, host_prefix: str = "chipbench."):
        pd = ProfileData.from_file(path)
        self.ops: Dict[str, _Ops] = {}
        self.host: List[Tuple[float, float, str]] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {ln.name: ln for ln in plane.lines}
                line = lines.get("XLA Ops") or lines.get("XLA Modules")
                if line is not None:
                    self.ops[plane.name] = _Ops(line.events)
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name.startswith(host_prefix):
                            self.host.append((e.start_ns, e.end_ns, e.name))
        wins = [(s, e) for s, e, n in self.host if n == WINDOW]
        if wins:
            self.window = (min(s for s, _ in wins), max(e for _, e in wins))
        else:
            spans = [(o.start.min(), o.end.max()) for o in self.ops.values()
                     if len(o.start)]
            self.window = ((min(s for s, _ in spans), max(e for _, e in spans))
                           if spans else (0.0, 0.0))
        self._busy = {d: clip(union(np.stack([o.start, o.end], 1)),
                              *self.window) for d, o in self.ops.items()}

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, device: str) -> List[Interval]:
        return self._busy[device]

    def busy_s(self) -> Dict[str, float]:
        """Seconds in which an op ran, per device."""
        return {d: length(b) / 1e9 for d, b in self._busy.items()}

    def idle_pct(self):
        """Idle share of the worst device, in percent; None without ops."""
        busy = self.busy_s()
        w = self.window_s
        if not busy or w <= 0:
            return None
        return 100.0 * (1.0 - min(busy.values()) / w)

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of ops whose name matches ``pattern``, over
        all devices, inside the window."""
        lo, hi = self.window
        return float(sum(o.clipped(lo, hi)[o.matching(pattern)
                                           & o.inside(lo, hi)].sum()
                         for o in self.ops.values()) / 1e9)

    def op_count(self, pattern: str) -> int:
        lo, hi = self.window
        return int(sum((o.matching(pattern) & o.inside(lo, hi)).sum()
                       for o in self.ops.values()))

    def annotations(self, prefix: str) -> List[Tuple[float, float, str]]:
        lo, hi = self.window
        return [(s, e, n) for s, e, n in self.host
                if n.startswith(prefix) and e > lo and s < hi]

    def busy_under(self, prefix: str) -> float:
        """Device busy seconds (worst device's union) inside the host
        annotations whose name starts with ``prefix``."""
        ann = union([(s, e) for s, e, _ in self.annotations(prefix)])
        if not ann or not self.ops:
            return 0.0
        return max(length(intersect(b, ann))
                   for b in self._busy.values()) / 1e9

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` op names that took the most device time (s); an op
        inside a loop counts within the loop's op too."""
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for o in self.ops.values():
            m = o.inside(lo, hi)
            sums = np.bincount(o.ids[m], weights=o.clipped(lo, hi)[m],
                               minlength=len(o.names))
            for i in np.flatnonzero(sums):
                n = o.names[i]
                key = n.split(" = ")[0] if " = " in n else n
                tot[key] = tot.get(key, 0.0) + float(sums[i])
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in top]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest idle gaps of the worst device, each named by
        the innermost benchmark annotation the host was inside at the gap's
        middle ("host" where it was inside none)."""
        if not self.ops:
            return []
        busy = self.busy_s()
        b = self._busy[min(busy, key=busy.get)]
        lo, hi = self.window
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = 0.5 * (s + e)
            inside = [(hs, he, n) for hs, he, n in self.host
                      if hs <= mid <= he and n != WINDOW]
            name = (min(inside, key=lambda h: h[1] - h[0])[2].split(":")[0]
                    if inside else "host")
            out.append([name, (e - s) / 1e9])
        return out
