"""What every run shares: the benchmark's files, the device check, the
compile cache and clock, the set-up split, spans, and the result line.

A cell is found by name in ``BENCHMARK.json``; its configuration, traffic
mix, driver, limits and metric readers are files found by the names there:

- ``configs/<config>.json``   the model pair and its sizes;
- ``traffic/<traffic>.json``  the mix's parameters, naming its driver;
- ``drivers/<driver>.py``     ``setup(ctx)``, ``window(ctx, state)`` and
                              ``check(ctx, state)``;
- ``limits/<cell>.json``      the limit of each number the check compares;
- ``metrics/<metric>.py``     ``read(run)``, one reader per metric.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import math
import os
import re
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH_DIR))
# Fixed, inside the checkout: the cache's key includes its path.
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")


class BenchError(Exception):
    """The run cannot go on (no chip, a file missing, a program that does
    not match its configuration)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_start() -> float:
    """``time.perf_counter()`` at the moment this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


# ---------------------------------------------------------------------------
# The benchmark's files
# ---------------------------------------------------------------------------
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = REPO) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str

    def driver(self):
        return load_module("drivers", self.traffic["driver"], self.bench_dir)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: Dict, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    root = os.path.dirname(os.path.dirname(bench_dir))
    cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    limits_path = os.path.join(bench_dir, "limits", f"{name}.json")
    limits = load_json(limits_path) if os.path.exists(limits_path) else {}
    return Cell(name=name, chips=int(w["chips"]), config=cfg,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                bench_dir=bench_dir)


# ---------------------------------------------------------------------------
# Device, compile cache, compile clock
# ---------------------------------------------------------------------------
def require_chips(n: int, platform: str = "tpu"):
    """The devices this run uses; no accelerator, or too few, is an error
    (there is no CPU fallback)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"no {platform.upper()}: JAX found "
                         f"{devs[0].platform} devices only")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def use_cache(cache_dir: str = CACHE_DIR) -> str:
    """Point JAX's persistent compilation cache at the fixed directory in
    the checkout, and cache every program, however quick to compile. The
    environment variable is set too, so that program code which reads it
    takes the same directory."""
    import jax
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class _MissLog(logging.Handler):
    """Names of the programs the persistent cache missed (and wrote)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: List[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Writing ") and "persistent compilation cache" in msg:
            self.names.append(msg.split()[1])


class CompileClock:
    """Seconds the backend spent compiling, and persistent-cache hits and
    misses, from JAX's monitoring events; the names of the missed programs
    from the cache's log."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self._log = _MissLog()
        lg = logging.getLogger("jax._src.compilation_cache")
        lg.setLevel(logging.DEBUG)
        lg.addHandler(self._log)
        lg.propagate = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def missed(self) -> List[str]:
        return list(self._log.names)

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "hits": self.hits, "misses": self.misses}


# ---------------------------------------------------------------------------
# The run's context
# ---------------------------------------------------------------------------
@dataclass
class Check:
    """One number compared with the plain reference, and its limit."""
    name: str
    value: float
    limit: Optional[float]

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                          # process start, perf_counter
    clock: Optional[CompileClock] = None
    marks: Dict[str, float] = field(default_factory=dict)
    records: Dict[str, Any] = field(default_factory=dict)
    log: Any = log

    @property
    def config(self) -> Dict:
        return self.cell.config

    @property
    def traffic(self) -> Dict:
        return self.cell.traffic

    def mark(self, name: str) -> None:
        """Close a stage of set-up (``import``, ``init``): the time, and the
        seconds compiled so far."""
        self.marks[name] = (time.perf_counter(),
                            self.clock.seconds if self.clock else 0.0)

    def key(self, salt: int = 0):
        """A PRNG key from the seed (any whole number up to 2**63)."""
        import jax
        k = jax.random.PRNGKey((self.seed >> 32) & 0xFFFFFFFF)
        return jax.random.fold_in(jax.random.fold_in(k, self.seed
                                                     & 0xFFFFFFFF), salt)

    def np_seed(self, salt: int = 0) -> int:
        return (self.seed * 1000003 + salt * 7919) % (2 ** 31)

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def limit(self, name: str) -> Optional[float]:
        return self.cell.limits.get(name)

    def checks(self, values: Dict[str, float]) -> List[Check]:
        """The numbers that have a limit; the others are logged as not
        compared (a run with none compared is not correct)."""
        for k, v in values.items():
            if self.limit(k) is None:
                self.log(f"check {k} {float(v)!r} not compared: no limit")
        return [Check(k, float(v), self.limit(k)) for k, v in values.items()
                if self.limit(k) is not None]


@contextlib.contextmanager
def trace_window(ctx: Context, log_dir: str):
    """Profile what runs inside, when the run traces."""
    if not ctx.trace:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
