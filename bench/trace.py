"""The reduction from a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps two
things on one clock: the operations that ran on each device, and the host
spans (``TraceAnnotation`` / TraceMe events) of every host thread.  The
functions below turn them into the numbers the per-layer readers report:

* busy time -- the union of the intervals in which an operation ran on a
  device; the idle share is 1 minus busy over the traced window;
* the device operations that took the most time;
* the idle gaps, each named by the host span open at the gap's middle.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field

__all__ = ["Timeline", "load", "merge", "clip", "busy_ns", "within", "gaps",
           "top_ops", "host_spans_at", "name_gaps"]

# the lines of a device plane that hold one event per executed operation,
# in order of preference: single ops, else whole programs
DEVICE_OP_LINES = ("XLA Ops", "XLA Modules")
# host spans that name what the host was doing (harness and program
# annotations), preferred over the runtime's own events when both are open
ANNOTATION_PREFIXES = ("bench:", "repro:")


@dataclass
class Timeline:
    """Device operations per device and host spans, in ns on one clock."""
    ops: dict = field(default_factory=dict)      # device -> [(name, t0, t1)]
    host: list = field(default_factory=list)     # [(name, t0, t1)]

    def spans_named(self, name: str) -> list:
        return sorted((t0, t1) for n, t0, t1 in self.host if n == name)

    def all_ops(self) -> list:
        return [op for ops in self.ops.values() for op in ops]


_DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Z]+:\d+$")


def _is_device_plane(name: str) -> bool:
    """One plane per accelerator chip ("/device:TPU:0"), not its
    sub-units' planes."""
    return bool(_DEVICE_PLANE.match(name))


def load(log_dir: str) -> Timeline:
    """The newest trace under ``log_dir``, reduced to a Timeline."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    tl = Timeline()
    for plane in data.planes:
        if _is_device_plane(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = next((lines[n] for n in DEVICE_OP_LINES if n in lines),
                        None)
            if line is None:
                continue
            tl.ops[plane.name] = sorted(
                ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in line.events), key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tl.host.extend((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events if ev.duration_ns > 0)
    return tl


def merge(intervals) -> list:
    """The union of (t0, t1) intervals, as sorted disjoint intervals."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1][1] = t1
        else:
            out.append([t0, t1])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi) -> list:
    """Intervals cut to the window [lo, hi]."""
    return [(max(t0, lo), min(t1, hi)) for t0, t1 in intervals
            if t1 > lo and t0 < hi]


def busy_ns(ops, lo, hi) -> float:
    """Time in [lo, hi] in which at least one of ``ops`` ran."""
    return float(sum(t1 - t0 for t0, t1 in
                     clip(merge((o[1], o[2]) for o in ops), lo, hi)))


def within(ops, windows) -> list:
    """The parts of ``ops`` that fall inside any of ``windows``."""
    out = []
    for lo, hi in merge(windows):
        out.extend((n, max(t0, lo), min(t1, hi)) for n, t0, t1 in ops
                   if t1 > lo and t0 < hi)
    return out


def gaps(ops, lo, hi) -> list:
    """The intervals of [lo, hi] in which no operation ran."""
    out, t = [], lo
    for t0, t1 in clip(merge((o[1], o[2]) for o in ops), lo, hi):
        if t0 > t:
            out.append((t, t0))
        t = max(t, t1)
    if t < hi:
        out.append((t, hi))
    return out


def top_ops(ops, k: int = 10) -> list:
    """[(name, seconds)] of the k operations with the most device time."""
    total = collections.Counter()
    for name, t0, t1 in ops:
        total[name] += t1 - t0
    return [(name, ns * 1e-9) for name, ns in total.most_common(k)]


def _pick(open_) -> str:
    """The innermost of the open spans: the latest opened, and of spans
    opened together the first to close."""
    if not open_:
        return "no host span"
    marked = [e for e in open_ if e[1].startswith(ANNOTATION_PREFIXES)]
    return max(marked or open_, key=lambda e: (e[0], -e[2]))[1]


def host_spans_at(host, points) -> list:
    """For each instant of ``points``: the host span open at it -- the most
    recently opened annotation if one is open, else the most recently
    opened event, else "no host span"."""
    spans = sorted(host, key=lambda s: s[1])
    order = sorted(range(len(points)), key=lambda i: points[i])
    out = [None] * len(points)
    active, j = [], 0
    for i in order:
        t = points[i]
        while j < len(spans) and spans[j][1] <= t:
            name, t0, t1 = spans[j]
            active.append((t0, name, t1))
            j += 1
        active = [a for a in active if a[2] > t]
        out[i] = _pick(active)
    return out


def name_gaps(ops, host, lo, hi, k: int = 10) -> list:
    """[(host span, seconds)]: idle time summed by the host span open at
    each gap's middle, the k largest."""
    idle = gaps(ops, lo, hi)
    names = host_spans_at(host, [(g0 + g1) / 2 for g0, g1 in idle])
    total = collections.Counter()
    for name, (g0, g1) in zip(names, idle):
        total[name] += g1 - g0
    return [(name, ns * 1e-9) for name, ns in total.most_common(k)]
