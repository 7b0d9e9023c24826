"""Unified observability: structured tracing, metrics, and the flight recorder.

One subsystem answers "what happened during that hop and what did it cost
each request" across the whole train→grow→serve lifecycle:

- **Spans & events** (:mod:`repro.obs.trace`) — ``span("hop.grow", gen=3)``
  context manager (thread-safe, monotonic clock, parent/child nesting) and
  point events, recorded into a bounded in-memory **flight recorder** ring
  that dumps as JSONL on demand and automatically on hop
  rollback/retry/watchdog-fire. Each span is also a
  ``jax.profiler.TraceAnnotation``, so a profiled run shows the program's
  spans on the device trace's clock, and every backend compile is counted
  by the span it happened in (``jax.compiles``, ``jax.compile_s``).
- **Metrics** (:mod:`repro.obs.metrics`) — typed counters, gauges, and
  fixed-bucket histograms (p50/p99 reconstructed from buckets, within one
  bucket width of a NumPy oracle) in a process-global named registry.
- **Export** (:mod:`repro.obs.export`, :mod:`repro.obs.prom`) — JSONL
  streaming (``--obs-log``), the human report (``--obs-report``),
  Prometheus text format + a ``/metrics`` HTTP endpoint
  (``--metrics-port``), and ``jax.profiler`` gating (``--obs-profile``).
- **Compute ledger** (:mod:`repro.obs.ledger`) — durable loss-vs-FLOPs
  accounting: an append-only JSONL with one record per train/LiGO step
  whose cursor rides checkpoint meta (kill-anywhere, resume
  bit-identical), plus ``savings_report`` — FLOPs-to-target-loss vs a
  from-scratch baseline ledger, the paper's headline metric.
- **Measured costs** (:mod:`repro.obs.costs`) — per compiled program,
  read FLOPs/bytes back from ``compiled.cost_analysis()`` through the
  roofline trip-count correction at compile time (never inside jit) and
  reconcile against the 6ND model (``ledger.flops.*`` gauges).
- **Timeline** (:mod:`repro.obs.timeline`) — Chrome-trace/Perfetto
  export of the span tree + ledger events (``--timeline``, or
  ``python -m repro.obs.timeline`` on an ``--obs-log`` file).

Naming scheme: ``<layer>.<unit>[_<ms|s>]`` with dots — ``serve.decode.step_ms``,
``serve.request.ttft_ms``, ``serve.spec.acc_ema``, ``serve.kv.pool_in_use_blocks``,
``hop.watchdog.budget_s``, ``kernels.launches``, ``core.traces``,
``jax.compiles``, ``ligo.chunk_ms``, ``traj.stage.train_ms``. Span names
mirror the subsystem:

- growth: ``grow`` (one ``grow()`` call) → ``ligo.init``, ``ligo.phase`` →
  ``ligo.chunk`` → ``ligo.batches`` / ``ligo.launch`` / ``ligo.sync``, and
  ``ligo.checkpoint``; then ``grow.params``, ``grow.moments``;
- serving: ``serve.prefill`` (admission to the first token on the host),
  ``serve.decode`` (a round's launch until its outputs are ready),
  ``serve.sample`` (the greedy picks, or the logits rows when sampling, to
  the host, picks, finishing; ``picked`` "device" or "host");
- the live hop: ``hop.warm``, ``hop.grow``, ``hop.cache-grow``, ``hop.swap``;
- trajectories: ``traj.train``, ``traj.grow``.

Hard rule: **instrumentation never runs inside jitted code.** Record at
host boundaries only — after ``block_until_ready``, around launches, or at
trace time for trace counters. ``set_enabled(False)`` is the global kill
switch (spans record and annotate nothing, metric writes early-return).
What the layer costs is measured on one TPU v5e, against the same code
without these spans and on the same seeds: the LiGO hop's ``hop_s`` moved
+0.46% and serving's inter-token p95 -0.05% (medians of two runs, inside
the run-to-run spread); a profiler session on top moved ``hop_s`` by
-1.6% and +1.6% and the inter-token p95 by -0.4% and +0.2%. An annotation costs about a microsecond outside a
profiler session. The CPU
``obs_overhead`` ratio in ``BENCH_growth.json`` gates nothing the product
runs on; retiring it is ROADMAP D7's.
"""
from repro.obs.metrics import (
    Counter, CounterGroup, Gauge, Histogram, LOG10_BUCKETS, MetricsRegistry,
    MS_BUCKETS, RATE_BUCKETS, REGISTRY, S_BUCKETS, counter, counter_group,
    gauge, histogram,
)
from repro.obs.trace import (
    FLIGHT, FlightRecorder, dump_dir, enabled, event, flight_dump,
    set_dump_dir, set_enabled, span,
)
from repro.obs.export import attach_jsonl, close_jsonl, profile, report
from repro.obs.prom import serve_metrics
from repro.obs.ledger import (
    RunLedger, active_ledger, attach_ledger, detach_ledger, read_ledger,
    savings_report,
)
from repro.obs.timeline import export_chrome_trace
from repro.obs import costs, prom

__all__ = [
    # metrics
    "Counter", "CounterGroup", "Gauge", "Histogram", "MetricsRegistry",
    "REGISTRY", "counter", "counter_group", "gauge", "histogram",
    "MS_BUCKETS", "S_BUCKETS", "RATE_BUCKETS", "LOG10_BUCKETS",
    # tracing
    "FLIGHT", "FlightRecorder", "span", "event", "flight_dump",
    "set_dump_dir", "dump_dir", "set_enabled", "enabled",
    # export
    "attach_jsonl", "close_jsonl", "report", "profile", "prom",
    "serve_metrics",
    # compute ledger + measured costs + timeline
    "RunLedger", "attach_ledger", "active_ledger", "detach_ledger",
    "read_ledger", "savings_report", "costs", "export_chrome_trace",
]
