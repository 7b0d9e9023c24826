"""Global on/off switch for the observability layer.

One module so :mod:`repro.obs.trace` and :mod:`repro.obs.metrics` can share
it without importing each other. Disabling turns ``span()`` into a no-op
context manager (no record, no profiler annotation; it still times its
block) and makes counter/gauge/histogram writes early-return.

Note :class:`repro.obs.metrics.CounterGroup` increments are *not* gated:
the kernel/trace/compile counters are functional instrumentation that tests
assert on (and they fire at trace or compile time, not per step), so they
keep counting even when the observability layer is switched off.
"""
from __future__ import annotations

_ENABLED = True


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    return _ENABLED
