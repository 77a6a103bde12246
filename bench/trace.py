"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy time in the harness's window, device time per
operation and per XLA program, and the longest idle gaps, each named by
what the host was doing.

All times are taken on the trace's own clock. The window is the
``bench.window`` annotation the harness writes around its measured loop.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import events as ev

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    """One traced window, in nanoseconds on the trace clock."""
    window: Interval
    #: busy time of each device used (union of its operations' intervals)
    busy_ns: List[float]
    #: device time per operation name, summed over the devices used
    ops_ns: Dict[str, float] = field(default_factory=dict)
    #: device time per XLA program name, summed over the devices used
    modules_ns: Dict[str, float] = field(default_factory=dict)
    #: longest idle gaps of the first device, longest first: (name, ns)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def mean_busy_ns(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns)

    def program_ns(self, part: str) -> float:
        """Device time of the XLA programs ``events.PROGRAMS[part]``
        matches."""
        pat = re.compile(ev.PROGRAMS[part])
        return sum(t for name, t in self.modules_ns.items()
                   if pat.search(name))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def reduce_xplane(path: str, devices: int, top: int = 10) -> TraceSummary:
    """Read ``path`` (``.xplane.pb``, or gzipped ``.xplane.pb.gz``) and
    reduce it over the first ``devices`` chips."""
    from jax.profiler import ProfileData
    with warnings.catch_warnings():
        # the profile bindings' stat types warn on introspection
        warnings.simplefilter("ignore", DeprecationWarning)
        if path.endswith(".gz"):
            with gzip.open(path, "rb") as f:
                data = ProfileData.from_serialized_xspace(f.read())
        else:
            data = ProfileData.from_file(path)
        return _reduce(data, devices, top)


def _reduce(data, devices: int, top: int) -> TraceSummary:
    dev_pat = re.compile(ev.DEVICE_PLANE)
    noise = re.compile(ev.HOST_NOISE)
    device_planes, annotations, host = [], [], []
    for plane in data.planes:
        m = dev_pat.match(plane.name)
        if m:
            device_planes.append((int(m.group(1)), plane))
        elif plane.name == ev.HOST_PLANE:
            for line in plane.lines:
                for name, start, dur in _events(line):
                    if name.startswith(ev.ANNOTATION_PREFIX):
                        annotations.append((name, start, start + dur))
                    elif dur > 0 and not noise.search(name):
                        host.append((name, start, start + dur))
    windows = [(a, b) for name, a, b in annotations if name == ev.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {ev.WINDOW!r} annotation, found "
                         f"{len(windows)}")
    window = windows[0]
    device_planes.sort(key=lambda p: p[0])
    if len(device_planes) < devices:
        raise ValueError(f"trace holds {len(device_planes)} device planes, "
                         f"the cell uses {devices}")
    busy, ops, modules, first_busy = [], {}, {}, None
    for _, plane in device_planes[:devices]:
        lines = {line.name: [(n, a, a + d) for n, a, d in _events(line)]
                 for line in plane.lines
                 if line.name in (ev.OPS_LINE, ev.MODULES_LINE)}
        mods = sorted(lines.get(ev.MODULES_LINE, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        intervals: Dict[str, List[Interval]] = {}
        for line_name, evs in lines.items():
            per_name = modules if line_name == ev.MODULES_LINE else ops
            for name, a, b in evs:
                iv = clip([(a, b)], window)
                if not iv:
                    continue
                intervals.setdefault(line_name, []).append(iv[0])
                if line_name == ev.OPS_LINE:
                    name = op_name(name, _enclosing(mods, starts, a))
                per_name[name] = per_name.get(name, 0.0) + (iv[0][1]
                                                            - iv[0][0])
        spans = (intervals.get(ev.OPS_LINE)
                 or intervals.get(ev.MODULES_LINE, []))
        merged = union(spans)
        busy.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged
    gaps = _gaps(first_busy or [], window)
    named = [(_name_gap(g, annotations, host), g[1] - g[0])
             for g in sorted(gaps, key=lambda g: g[0] - g[1])[:top]]
    return TraceSummary(window=window, busy_ns=busy, ops_ns=ops,
                        modules_ns=modules, gaps=named)


def op_name(hlo: str, module: Optional[str]) -> str:
    """``<program>/<op>`` from an XLA op event's HLO text (``%while.14 =
    ...``) and the name of the program event around it."""
    op = hlo.split(" = ", 1)[0].strip()
    prog = module.split("(", 1)[0] if module else "?"
    return f"{prog}/{op}"


def _enclosing(mods: List[tuple], starts: List[float],
               t: float) -> Optional[str]:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][1] <= t <= mods[i][2]:
        return mods[i][0]
    return None


def _gaps(merged: List[Interval], window: Interval) -> List[Interval]:
    out, t = [], window[0]
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def _name_gap(gap: Interval, annotations, host) -> str:
    """The innermost harness annotation around the gap's middle, and the
    host event that covers most of the gap."""
    mid = (gap[0] + gap[1]) / 2
    inner: Optional[tuple] = None
    for name, a, b in annotations:
        if a <= mid <= b and (inner is None or b - a < inner[2] - inner[1]):
            inner = (name, a, b)
    best, best_cover = None, 0.0
    for name, a, b in host:
        cover = _overlap(gap, (a, b))
        if cover > best_cover:
            best, best_cover = name, cover
    where = inner[0] if inner else "outside"
    return f"{where} > {best}" if best else where


class Profile:
    """The jax profiler around a window: ``start`` before it, ``stop``
    after, then ``summary`` reduces the trace (and deletes the file)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-function Python events
        opts.host_tracer_level = 2       # runtime events name idle gaps
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def summary(self, devices: int) -> TraceSummary:
        path = find_xplane(self.log_dir)
        try:
            return reduce_xplane(path, devices)
        finally:
            os.remove(path)
