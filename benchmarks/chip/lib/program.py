"""The program's own spans and compiles, on the device trace's clock.

The program records its spans (``repro.obs.span``) and its backend compiles
(``jax.compile`` events, each naming the innermost span open on the
compiling thread) in the obs flight ring, stamped in milliseconds of
``perf_counter`` since ``repro.obs.trace.EPOCH``. The harness's trace
reduction keeps only its own ``bench.*`` spans, so this module puts the
ring's records on the trace's clock itself:

- a first offset from the window's start on the host clock (process start
  plus ``setup_s``), where the profiler's session starts;
- then the offset, within ``SEARCH_S`` of that, at which most of the
  program's outermost spans on the window's thread lie inside a ``bench.*``
  span (the harness wraps every call into the program in one), taken at
  the earliest start the nesting allows.

From the records inside the traced window it reduces:

- ``idle_by_program_span``: each stretch of the window in which no device
  op runs, split at the program's span edges, every piece given to the
  innermost program span covering it (a span of the window's thread first,
  else one of another thread, else ``"none"``), keyed by the span's path
  from its outermost ancestor (``grow/ligo.phase/ligo.chunk/ligo.launch``);
- the compiles in the window, by span.

A program that records no such spans or compiles (one older than them)
gives None, and so do the readers built on it.
"""
from __future__ import annotations

import bisect
import json
import os
import threading
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.chip.lib import harness as H
from benchmarks.chip.lib import trace as T

SEARCH_S = 0.1          # how far the nesting may move the first offset
SEP = "/"               # joins a span's path


@dataclass
class Span:
    """One program span on the trace's clock (ns)."""
    name: str
    start: float
    end: float
    thread: str
    path: str
    attrs: Dict = field(default_factory=dict)

    @property
    def dur_ms(self) -> float:
        return (self.end - self.start) * 1e-6


@dataclass
class Window:
    """The program's records inside one traced window."""
    spans: List[Span]                       # starting inside the window
    compiles: List[Dict]                    # {"span", "secs"}
    idle: Dict[str, float]                  # idle seconds by span path

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def idle_within(self, name: str, *, outside: Sequence[str] = ()
                    ) -> float:
        """Idle seconds in pieces whose path holds ``name`` and none of
        ``outside``."""
        total = 0.0
        for path, v in self.idle.items():
            parts = path.split(SEP)
            if name in parts and not any(o in parts for o in outside):
                total += v
        return total


def ring():
    """(records of the obs ring, the epoch of their ``t_ms``), or None
    when the program records neither spans on this clock nor compiles."""
    try:
        from repro.obs import trace as OT
    except ImportError:
        return None
    epoch = getattr(OT, "EPOCH", None)
    if epoch is None or not hasattr(OT, "COMPILE_EVENT"):
        return None
    return OT.FLIGHT.events(), epoch


def _paths(records: List[Dict]) -> Dict[int, str]:
    by_id = {r["span_id"]: r for r in records if r.get("type") == "span"}
    out: Dict[int, str] = {}

    def path(r) -> str:
        sid = r["span_id"]
        if sid not in out:
            parent = by_id.get(r.get("parent_id"))
            out[sid] = (r["name"] if parent is None
                        else path(parent) + SEP + r["name"])
        return out[sid]

    for r in by_id.values():
        path(r)
    return out


def align(outer: List[Tuple[float, float]], host: List[T.Event],
          first: float, search_ns: float = SEARCH_S * 1e9) -> float:
    """The offset (ns, added to a span's own ns) at which most of
    ``outer`` (start, end) lie inside one of the ``host`` spans, searched
    within ``search_ns`` of ``first``; of the stretches of offsets that do
    best, the one nearest ``first``, at its lowest end. ``first`` when
    none lies inside any."""
    spans = sorted(((e.start, e.end) for e in host), key=lambda e: e[0])
    starts = [s for s, _ in spans]
    longest = max((e - s for s, e in spans), default=0.0)
    lo_d, hi_d = first - search_ns, first + search_ns
    edges = []
    for s, e in outer:
        i = bisect.bisect_left(starts, s + lo_d - longest)
        j = bisect.bisect_right(starts, s + hi_d)
        for bs, be in spans[i:j]:
            a, b = max(bs - s, lo_d), min(be - e, hi_d)
            if a <= b:
                edges.append((a, 0))        # opens before it closes
                edges.append((b, 1))
    edges.sort()
    best, best_dist, best_at, n = 0, None, first, 0
    for i, (x, kind) in enumerate(edges):
        n += 1 if kind == 0 else -1
        if kind == 0 and (i + 1 == len(edges) or edges[i + 1][1] == 1
                          or edges[i + 1][0] > x):
            # x opens a stretch held by n spans until the next edge
            end = edges[i + 1][0] if i + 1 < len(edges) else x
            dist = 0.0 if x <= first <= end else min(abs(x - first),
                                                     abs(end - first))
            if n > best or (n == best and dist < best_dist):
                best, best_dist, best_at = n, dist, x
    return best_at


def idle_by_program_span(trace: T.Trace, spans: List[Span], thread: str
                         ) -> Dict[str, float]:
    """Idle seconds of the devices in the window (averaged over them, as
    ``trace.summarize`` does), by the path of the innermost program span
    covering each piece, a span of ``thread`` first."""
    lo, hi = T.window_of(trace)
    edges = sorted({lo, hi} | {x for s in spans for x in (s.start, s.end)
                               if lo < x < hi})
    out: Dict[str, float] = {}
    n_dev = len(trace.devices)
    by_start = sorted(spans, key=lambda s: s.start)
    for evs in trace.devices.values():
        gaps = T.gaps(evs, lo, hi)
        k = 0
        active: List[Span] = []
        nxt = 0
        for a, b in zip(edges, edges[1:]):
            while nxt < len(by_start) and by_start[nxt].start <= a:
                active.append(by_start[nxt])
                nxt += 1
            active = [s for s in active if s.end > a]
            while k < len(gaps) and gaps[k][1] <= a:
                k += 1
            idle, j = 0.0, k
            while j < len(gaps) and gaps[j][0] < b:
                idle += T.overlap(gaps[j], (a, b))
                j += 1
            if idle <= 0:
                continue
            mine = [s for s in active if s.thread == thread] or active
            key = (max(mine, key=lambda s: (s.start, -s.end)).path
                   if mine else "none")
            out[key] = out.get(key, 0.0) + idle * 1e-9 / n_dev
    return out


def window(run) -> Optional[Window]:
    """The program's records in the run's traced window (computed once a
    run; None without a trace or without the program's records)."""
    if hasattr(run, "_program_window"):
        return run._program_window
    run._program_window = None
    got = ring()
    if run.trace is None or got is None or "setup_s" not in run.records:
        return None
    records, epoch = got
    thread = threading.current_thread().name
    lo, hi = T.window_of(run.trace)
    # the profiler's clock reads 0 where its session started, just after
    # the window's start on the host clock
    t_window = H.process_start() + run.records["setup_s"]
    first = (epoch - t_window) * 1e9
    paths = _paths(records)
    mine = [(r["t_ms"] * 1e6, (r["t_ms"] + r["dur_ms"]) * 1e6)
            for r in records if r.get("type") == "span"
            and r.get("parent_id") is None and r.get("thread") == thread
            and lo - SEARCH_S * 1e9 <= r["t_ms"] * 1e6 + first
            <= hi + SEARCH_S * 1e9]
    host = [e for e in run.trace.host if e.name != T.WINDOW_SPAN]
    off = align(mine, host, first)
    spans, compiles = [], []
    for r in records:
        t = r["t_ms"] * 1e6 + off
        if r.get("type") == "span":
            end = t + r["dur_ms"] * 1e6
            if end > lo and t < hi:
                spans.append(Span(r["name"], t, end, r.get("thread", ""),
                                  paths[r["span_id"]],
                                  dict(r.get("attrs") or {})))
        elif r.get("name") == "jax.compile" and lo <= t <= hi:
            compiles.append(dict(r.get("attrs") or {}))
    idle = idle_by_program_span(run.trace, spans, thread)
    inside = [s for s in spans if lo <= s.start <= hi]
    win = Window(spans=inside, compiles=compiles, idle=idle)
    run._program_window = win
    _report(run, win)
    return win


def _report(run, win: Window, n: int = 5) -> None:
    """Log the window's compiles by span and the largest idle pieces, and
    add the idle split to ``.out/last_trace.json``."""
    by_span = Counter(c.get("span", "none") for c in win.compiles)
    H.log(f"program: {len(win.compiles)} backend compiles in the window, "
          f"by span {dict(by_span)}")
    top = sorted(win.idle.items(), key=lambda kv: -kv[1])[:n]
    H.log("program spans holding device idle: " + (", ".join(
        f"{k} {v:.4f} s" for k, v in top) or "none"))
    path = os.path.join(run.cell.bench_dir, ".out", "last_trace.json")
    if os.path.exists(path):
        with open(path) as f:
            saved = json.load(f)
        saved["idle_by_program_span"] = win.idle
        with open(path, "w") as f:
            json.dump(saved, f)


def per_hop(run, value: float) -> Optional[float]:
    hops = run.records.get("hops")
    return value / hops if hops else None


def p95(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 95)) if values else None
