"""Measured-cost pass: reconcile the roofline model against compiled XLA.

The roofline layer *models* compute (``6·N·tokens`` per train step); XLA
*knows* what it actually compiled. This module reads the truth back at
compile time — never inside jit — by AOT-lowering a jitted function on
example (or abstract) arguments and pulling three sources per program:

- ``compiled.cost_analysis()`` — XLA's own flop/byte counts. XLA counts a
  ``while`` body **once**, so for scan-shaped programs (the LiGO chunk)
  this undercounts by the trip count.
- :func:`repro.roofline.collect_hlo_stats` over ``compiled.as_text()`` —
  the repo's HLO walker, which trip-count-corrects while bodies via the
  ``known_trip_count`` annotation. Its ``dot_flops`` column counts dots
  only (no elementwise), so it *under*counts flat programs.
- ``compiled.memory_analysis()`` — argument/output/temp footprints.

The measured FLOPs number is ``max(cost_analysis flops, trip-corrected
dot_flops)``: on a scan program the corrected dot count dominates the
once-counted cost analysis; on a flat program the cost analysis (which
includes elementwise work) dominates the dot-only count. Per-device
numbers are scaled by ``n_devices`` for SPMD programs so they compare
against the global modelled count.

A program that cannot be lowered or compiled, or whose backend exposes no
cost analysis, is not measured: the failure is counted per program in the
``ledger.measure.failures`` counter group and printed to stderr, and the
caller carries on unmeasured. Entry points that must not run unmeasured
check that group.

Every measurement lands in :data:`MEASUREMENTS`, publishes the
``ledger.flops.modelled`` / ``ledger.flops.measured`` gauges plus the
``ledger.flops.ratio`` reconciliation gauge (measured/modelled), and
emits a ``ledger.measure`` event on the flight recorder. Consumers
(trajectory runner, LiGO phase, serving install) use
``flops_per_unit`` — measured FLOPs divided by the steps/tokens one call
advances — as the per-step increment for the run ledger and for the
autogrow telemetry's cum-FLOPs axis.

AOT lowering compiles the program a second time (the jit cache is not
populated by ``.lower().compile()``), so callers only run the pass when
a ledger is active. Determinism: the same program text yields the same
counts, so a resumed run that re-measures at compile time reproduces the
original run's measured column exactly.
"""
from __future__ import annotations

import sys
import threading
from typing import Any, Dict, Optional

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

__all__ = ["measure_compiled", "measure_jitted", "measurement",
           "MEASUREMENTS", "clear_measurements"]

_LOCK = threading.Lock()

#: name -> latest measurement dict for that program.
MEASUREMENTS: Dict[str, Dict[str, Any]] = {}


def clear_measurements() -> None:
    with _LOCK:
        MEASUREMENTS.clear()


def measurement(name: str) -> Optional[Dict[str, Any]]:
    with _LOCK:
        return MEASUREMENTS.get(name)


def _failed(name: str, what: str, err: Exception) -> None:
    """Count and report a program the pass could not measure."""
    _metrics.counter_group("ledger.measure.failures").inc(name)
    _trace.event("ledger.measure_failed", program=name, stage=what,
                 error=f"{type(err).__name__}: {err}")
    print(f"[obs] measured-cost pass: {what} of {name} failed: "
          f"{type(err).__name__}: {err}", file=sys.stderr)


def measure_compiled(name: str, compiled, *,
                     modelled_flops: Optional[float] = None,
                     n_devices: int = 1,
                     per_call_units: float = 1.0) -> Optional[Dict[str, Any]]:
    """Measure an already-compiled executable (``jitted.lower().compile()``).

    ``per_call_units`` is how many ledger units (train steps, LiGO steps,
    decoded tokens) one call of the program advances — ``flops_per_unit``
    divides by it. ``modelled_flops`` is the roofline prediction for one
    call (same units), enabling the reconciliation ratio. Returns the
    measurement dict, or ``None`` when the backend exposes no cost
    analysis; that failure is counted (see the module docstring).
    """
    try:
        cost = dict(compiled.cost_analysis() or {})
    except Exception as e:  # backends without a cost model raise here
        _failed(name, "cost analysis", e)
        return None
    try:
        from repro.roofline import collect_hlo_stats
        stats = collect_hlo_stats(compiled.as_text())
    except Exception:
        stats = {}
    mem: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception:
        pass

    nd = max(int(n_devices), 1)
    raw = float(cost.get("flops", 0.0) or 0.0) * nd
    dot = float(stats.get("dot_flops", 0.0) or 0.0) * nd
    flops = max(raw, dot)
    units = max(float(per_call_units), 1e-12)
    rec: Dict[str, Any] = {
        "name": name,
        "flops": flops,
        "flops_cost_analysis": raw,
        "flops_dot_corrected": dot,
        "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0) * nd,
        "hbm_bytes": float(stats.get("hbm_bytes", 0.0) or 0.0) * nd,
        "trip_annotations": int(stats.get("n_trip_annotations", 0) or 0),
        "n_devices": nd,
        "per_call_units": float(per_call_units),
        "flops_per_unit": flops / units,
        "memory": mem,
    }
    if modelled_flops is not None and modelled_flops > 0:
        rec["modelled_flops"] = float(modelled_flops)
        rec["ratio"] = flops / float(modelled_flops)
    with _LOCK:
        MEASUREMENTS[name] = rec
    _metrics.gauge("ledger.flops.measured").set(rec["flops_per_unit"])
    if modelled_flops is not None and modelled_flops > 0:
        _metrics.gauge("ledger.flops.modelled").set(
            float(modelled_flops) / float(per_call_units))
        _metrics.gauge("ledger.flops.ratio").set(rec["ratio"])
    _trace.event("ledger.measure", program=name, flops=flops,
                 modelled=modelled_flops, ratio=rec.get("ratio"),
                 n_devices=nd, trip_annotations=rec["trip_annotations"])
    return rec


def measure_jitted(name: str, jitted, *args,
                   modelled_flops: Optional[float] = None,
                   n_devices: int = 1,
                   per_call_units: float = 1.0) -> Optional[Dict[str, Any]]:
    """AOT-lower + compile ``jitted`` on ``args`` and measure it.

    ``args`` may mix concrete arrays and ``jax.ShapeDtypeStruct`` trees —
    lowering never executes the program (donated buffers stay live).
    A lowering or compile failure is counted and reported, and returns
    ``None``: the caller's job (training) goes on unmeasured.
    """
    try:
        compiled = jitted.lower(*args).compile()
    except Exception as e:  # any compiler error; counted, never hidden
        _failed(name, "compile", e)
        return None
    return measure_compiled(name, compiled, modelled_flops=modelled_flops,
                            n_devices=n_devices,
                            per_call_units=per_call_units)
