"""Structured tracing: spans, events, and the flight recorder.

``span(name, **attrs)`` is a zero-dependency context manager: monotonic
clock (``time.perf_counter``), thread-safe (per-thread parent stacks), and
parent/child nesting — a span opened inside another span on the *same*
thread records that span as its parent, so a dump reconstructs the tree.
``event(name, **attrs)`` records a point-in-time marker.

Both land in the :class:`FlightRecorder` — a bounded in-memory ring
(``deque(maxlen=...)``) that can be dumped as JSONL on demand
(:func:`flight_dump`) and is dumped automatically by the hop controller on
rollback/retry/watchdog-fire, so every chaos path leaves a forensic trail.
An optional *sink* (attached by ``--obs-log``) additionally streams every
record as it happens.

Records are plain dicts with a fixed key order, so the JSONL is both
machine-parseable and grep-able (``grep '"name": "hop.grow"' dump.jsonl``):

    {"type": "span", "name": "hop.grow", "span_id": 7, "parent_id": null,
     "thread": "hop-grow-1", "t_ms": 123.4, "dur_ms": 56.7,
     "attrs": {"attempt": 1}}

``t_ms`` is milliseconds since process-local epoch (first import of this
module); ``dur_ms`` is the span's wall time. Spans are recorded at *exit*
(they carry ``dur_ms``); ordering in the ring is therefore by end time —
sort by ``t_ms`` to rebuild the timeline. A span that exits via an
exception carries an ``error`` field with the exception repr.

**On the device trace's clock.** Once jax is imported, every span also
enters ``jax.profiler.TraceAnnotation(name, **attrs)`` for its lifetime,
with the scalar attrs given at entry as the annotation's arguments (attrs
written mid-span reach the ring, not the profiler). A profiled run
(``--obs-profile DIR``) then shows the program's spans beside the device
ops they launch. Outside a profiler session an annotation costs about a
microsecond.

**Compiles by span.** At the first span after jax is imported, a
``jax.monitoring`` listener is registered (once) for the backend compile
event. Each compile adds to the counter groups ``jax.compiles`` (count) and
``jax.compile_s`` (seconds), keyed by the innermost open span on the
compiling thread (``"none"`` outside every span), and records a
``jax.compile`` event (``span``, ``secs``) in the ring.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from repro.obs import _state
from repro.obs.metrics import counter_group

__all__ = [
    "FlightRecorder", "FLIGHT", "span", "event", "flight_dump",
    "set_dump_dir", "dump_dir", "set_enabled", "enabled",
]

set_enabled = _state.set_enabled
enabled = _state.enabled

# ``time.perf_counter()`` at which the records' ``t_ms`` reads 0
EPOCH = time.perf_counter()
_SPAN_IDS = itertools.count(1)
_TLS = threading.local()

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES = counter_group("jax.compiles")
_COMPILE_S = counter_group("jax.compile_s")
_ANNOTATION = None      # jax.profiler.TraceAnnotation, once jax is imported
_HOOK_LOCK = threading.Lock()


def _now_ms() -> float:
    return (time.perf_counter() - EPOCH) * 1e3


class FlightRecorder:
    """Bounded ring of trace records, dumpable as JSONL."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._sink: Optional[Callable[[dict], None]] = None
        self._dropped = 0  # records evicted from the ring (bounded memory)

    @property
    def capacity(self) -> int:
        return self._ring.maxlen or 0

    def record(self, ev: dict) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(ev)
            sink = self._sink
        if sink is not None:
            try:
                sink(ev)
            except Exception:  # a broken sink must never kill the workload
                pass

    def events(self, *, type: Optional[str] = None,
               prefix: Optional[str] = None) -> List[dict]:
        """Snapshot of the ring, oldest first, optionally filtered."""
        with self._lock:
            evs = list(self._ring)
        if type is not None:
            evs = [e for e in evs if e.get("type") == type]
        if prefix is not None:
            evs = [e for e in evs if str(e.get("name", "")).startswith(prefix)]
        return evs

    def set_sink(self, sink: Optional[Callable[[dict], None]]) -> None:
        with self._lock:
            self._sink = sink

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._dropped = 0

    def dump(self, path: str, *, reason: str = "on-demand") -> str:
        """Write the ring (oldest first) to ``path`` as JSONL."""
        with self._lock:
            evs = list(self._ring)
            dropped = self._dropped
        with open(path, "w") as fh:
            fh.write(json.dumps({
                "type": "dump", "reason": reason, "t_ms": _now_ms(),
                "n_records": len(evs), "ring_evicted": dropped,
            }) + "\n")
            for ev in evs:
                fh.write(json.dumps(ev) + "\n")
        return path


# Room for a whole traced window: a serving loop at tens of decode rounds a
# second records about a hundred spans a second, and the window's readers
# need its start (the hop a third of the way in) as much as its end.
FLIGHT = FlightRecorder(capacity=1 << 16)

_DUMP_DIR: Optional[str] = None
_DUMP_SEQ = itertools.count(1)
_DUMP_LOCK = threading.Lock()


def set_dump_dir(d: Optional[str]) -> None:
    """Directory for automatic flight-recorder dumps (None disables them)."""
    global _DUMP_DIR
    _DUMP_DIR = d


def dump_dir() -> Optional[str]:
    return _DUMP_DIR


def flight_dump(reason: str) -> Optional[str]:
    """Dump the ring to ``<dump_dir>/flightrec-NNN-<reason>.jsonl``.

    No-op (returns None) when no dump dir is configured — the ring still
    holds everything for an on-demand :meth:`FlightRecorder.dump`.
    """
    d = _DUMP_DIR
    if d is None:
        return None
    event("obs.dump", reason=reason)
    safe = "".join(c if (c.isalnum() or c in "-_.") else "-" for c in reason)
    with _DUMP_LOCK:
        n = next(_DUMP_SEQ)
        path = os.path.join(d, f"flightrec-{n:03d}-{safe}.jsonl")
        FLIGHT.dump(path, reason=reason)
    return path


def _stack() -> list:
    """This thread's open spans, outermost first, as ``(span_id, name)``."""
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _on_duration(name: str, secs: float, **_) -> None:
    if name != COMPILE_EVENT:
        return
    st = _stack()
    where = st[-1][1] if st else "none"
    _COMPILES.inc(where)
    _COMPILE_S.inc(where, secs)
    event("jax.compile", span=where, secs=round(secs, 6))


def _annotation():
    """``jax.profiler.TraceAnnotation`` once the process has imported jax
    (None before: obs itself never imports it). The first call that finds
    jax also registers the compile listener."""
    global _ANNOTATION
    if _ANNOTATION is None and sys.modules.get("jax") is not None:
        with _HOOK_LOCK:
            if _ANNOTATION is None:
                import jax
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
                _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION


def _scalars(attrs: Dict[str, object]) -> Dict[str, object]:
    return {k: v for k, v in attrs.items()
            if isinstance(v, (bool, int, float, str))}


class _Span:
    """Context manager recording one span on exit. Mutate ``attrs`` inside
    the block to attach facts discovered mid-span (e.g. the cache-migration
    mode picked); read ``dur_ms`` after the block for the measured wall."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "_t0", "dur_ms",
                 "_ann")

    def __init__(self, name: str, attrs: Dict[str, object]):
        self.name = name
        self.attrs = attrs
        self.span_id = next(_SPAN_IDS)
        self.parent_id: Optional[int] = None
        self._t0 = 0.0
        self.dur_ms: Optional[float] = None
        self._ann = None

    def __enter__(self) -> "_Span":
        st = _stack()
        self.parent_id = st[-1][0] if st else None
        st.append((self.span_id, self.name))
        ann = _annotation()
        if ann is not None:
            self._ann = ann(self.name, **_scalars(self.attrs))
            self._ann.__enter__()
        self._t0 = _now_ms()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = _now_ms()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1][0] == self.span_id:
            st.pop()
        self.dur_ms = round(t1 - self._t0, 3)
        rec = {
            "type": "span", "name": self.name, "span_id": self.span_id,
            "parent_id": self.parent_id,
            "thread": threading.current_thread().name,
            "t_ms": round(self._t0, 3), "dur_ms": self.dur_ms,
        }
        if exc is not None:
            rec["error"] = repr(exc)
        rec["attrs"] = self.attrs
        FLIGHT.record(rec)
        return False  # never swallow


class _NoopSpan:
    """A disabled span: records nothing and annotates nothing, but still
    times its block, so callers that read ``dur_ms`` work either way."""

    __slots__ = ("attrs", "dur_ms", "_t0")

    def __init__(self):
        self.attrs: Dict[str, object] = {}
        self.dur_ms: Optional[float] = None

    def __enter__(self) -> "_NoopSpan":
        self._t0 = _now_ms()
        return self

    def __exit__(self, *a) -> bool:
        self.dur_ms = round(_now_ms() - self._t0, 3)
        return False


def span(name: str, **attrs):
    """Open a span: ``with span("hop.grow", gen=3) as sp: ...``."""
    if not _state.enabled():
        return _NoopSpan()  # fresh: callers may write attrs
    return _Span(name, attrs)


def event(name: str, **attrs) -> None:
    """Record a point-in-time marker (e.g. ``hop.rollback``)."""
    if not _state.enabled():
        return
    st = _stack()
    FLIGHT.record({
        "type": "event", "name": name,
        "parent_id": st[-1][0] if st else None,
        "thread": threading.current_thread().name,
        "t_ms": round(_now_ms(), 3),
        "attrs": attrs,
    })
