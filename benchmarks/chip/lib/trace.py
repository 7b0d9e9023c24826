"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Only the process that holds the chip can trace it. The run wraps its
measured window in a host ``TraceAnnotation`` named ``bench.window`` and
every call into a layer in a ``bench.<layer>`` annotation; the device
planes hold one event per executed operation. From those two kinds of
event this module computes:

- busy: the union of the device-op intervals inside the window, per device;
- idle share: 1 - busy / window;
- time per operation or program name (summed device durations);
- idle gaps: the stretches of the window in which no device op ran, each
  named by the ``bench.*`` host span that overlaps it most ("idle" when
  none does).

Device and host timestamps share one clock in the trace, which is what lets
a gap be named by the host span it falls in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# Lines of a TPU device plane: one event per HLO op, and one per program.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class Event:
    name: str
    start: float                        # ns
    end: float                          # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """The events of one trace that the reduction needs."""
    devices: Dict[str, List[Event]] = field(default_factory=dict)   # ops
    modules: Dict[str, List[Event]] = field(default_factory=dict)   # programs
    host: List[Event] = field(default_factory=list)                 # bench.*


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _is_tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


# How a CPU trace holds the same things (for rehearsals; no CPU number is
# ever reported as a device metric): XLA's CPU ops run on host threads.
CPU_LINES = {"device_plane": lambda n: n == "/host:CPU",
             "ops_line": lambda n: n.startswith("tf_XLA"),
             "modules_line": lambda n: False}


def load(path_or_data, *, device_plane: Callable[[str], bool] = _is_tpu_plane,
         ops_line: Callable[[str], bool] = lambda n: n == OPS_LINE,
         modules_line: Callable[[str], bool] = lambda n: n == MODULES_LINE,
         ) -> Trace:
    """Read a trace (a path, or a ``jax.profiler.ProfileData``).

    ``device_plane``/``ops_line``/``modules_line`` pick the planes and lines
    that hold device work; the defaults are a TPU's. Zero-length events are
    markers, not work, and are dropped.
    """
    if isinstance(path_or_data, (str, os.PathLike)):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(str(path_or_data))
    else:
        data = path_or_data
    tr = Trace()
    for plane in data.planes:
        dev = device_plane(plane.name)
        for line in plane.lines:
            if dev and ops_line(line.name):
                dest = tr.devices.setdefault(plane.name, [])
            elif dev and modules_line(line.name):
                dest = tr.modules.setdefault(plane.name, [])
            else:
                dest = tr.host
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                if dest is tr.host and not e.name.startswith(SPAN_PREFIX):
                    continue
                dest.append(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return tr


def window_of(tr: Trace) -> Interval:
    """The ``bench.window`` span: the first one's start to the last's end."""
    ws = [e for e in tr.host if e.name == WINDOW_SPAN]
    if not ws:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(e.start for e in ws), max(e.end for e in ws)


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((x.start, x.end) for x in events),
                                       lo, hi))


def gaps(events: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """Stretches of [lo, hi] in which no event runs."""
    out, t = [], lo
    for s, e in union(((x.start, x.end) for x in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


class SpanIndex:
    """The ``bench.*`` host spans other than the window, sorted by start,
    for naming gaps."""

    def __init__(self, host: List[Event]):
        self.spans = sorted((e for e in host if e.name != WINDOW_SPAN),
                            key=lambda e: e.start)
        self.starts = [e.start for e in self.spans]
        self.longest = max((e.dur for e in self.spans), default=0.0)

    def name_gap(self, gap: Interval) -> str:
        """The span that overlaps ``gap`` most; "idle" when none does."""
        best, name = 0.0, "idle"
        i = bisect.bisect_left(self.starts, gap[0] - self.longest)
        j = bisect.bisect_left(self.starts, gap[1])
        for e in self.spans[i:j]:
            o = overlap(gap, (e.start, e.end))
            if o > best:
                best, name = o, e.name
        return name


def time_by_name(events: Iterable[Event], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Seconds per event name inside [lo, hi] (events clipped to it)."""
    out: Dict[str, float] = {}
    for e in events:
        o = overlap((e.start, e.end), (lo, hi))
        if o > 0:
            out[e.name] = out.get(e.name, 0.0) + o * 1e-9
    return out


def idle_share_between(tr: Trace, t_from: float, t_to: float) -> float:
    """Idle share of the devices from ``t_from`` to ``t_to`` seconds after
    the window opened (clipped to the window)."""
    lo, hi = window_of(tr)
    a, b = lo + t_from * 1e9, min(hi, lo + t_to * 1e9)
    if b <= a:
        raise ValueError("the stretch lies outside the window")
    busy = [busy_ns(evs, a, b) for evs in tr.devices.values()]
    return 1.0 - sum(busy) / len(busy) / (b - a)


@dataclass
class Summary:
    """What one traced window reduces to."""
    window_s: float
    busy_s: float                           # averaged over the devices
    idle_share: float
    ops_s: Dict[str, float]                 # all devices, summed
    modules_s: Dict[str, float]
    op_counts: Dict[str, int]
    idle_by_span: Dict[str, float]          # idle seconds, by host span
    n_devices: int

    def breakdown(self, n: int = 10, width: int = 200) -> Dict[str, list]:
        """The ``n`` device ops with most time (a loop's time holds its
        body's ops) and the ``n`` largest idle totals by host span. An op
        is named by the first ``width`` characters of its HLO text: its
        name and the start of its shapes."""
        top = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k[:width], v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}

    def time_of(self, pattern: str, *, modules: bool = False) -> float:
        src = self.modules_s if modules else self.ops_s
        rx = re.compile(pattern)
        return sum(v for k, v in src.items() if rx.search(k))

    def count_of(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_counts.items() if rx.search(k))


def summarize(tr: Trace) -> Summary:
    lo, hi = window_of(tr)
    if not tr.devices:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(evs, lo, hi) for evs in tr.devices.values()]
    busy_s = sum(busy) / len(busy) * 1e-9
    window_s = (hi - lo) * 1e-9
    ops: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for evs in tr.devices.values():
        for k, v in time_by_name(evs, lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v
        for e in evs:
            if overlap((e.start, e.end), (lo, hi)) > 0:
                counts[e.name] = counts.get(e.name, 0) + 1
    mods: Dict[str, float] = {}
    for evs in tr.modules.values():
        for k, v in time_by_name(evs, lo, hi).items():
            mods[k] = mods.get(k, 0.0) + v
    idle: Dict[str, float] = {}
    index = SpanIndex(tr.host)
    for evs in tr.devices.values():
        for g in gaps(evs, lo, hi):
            k = index.name_gap(g)
            idle[k] = idle.get(k, 0.0) + (g[1] - g[0]) * 1e-9 / len(busy)
    return Summary(window_s=window_s, busy_s=busy_s,
                   idle_share=1.0 - busy_s / window_s if window_s else 0.0,
                   ops_s=ops, modules_s=mods, op_counts=counts,
                   idle_by_span=idle, n_devices=len(busy))
