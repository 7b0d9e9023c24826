"""The live hop: grow the serving model without dropping a session.

Stage machine (driven by :meth:`HopController.poll` between decode steps):

1. **grow** — materialise the grown params double-buffered through the
   memoised ``GrowthPlan`` executor (operator pre-placed on the serving mesh
   via ``place_operator``). Runs in a background thread by default, so the
   old weights keep decoding; a ``HopWatchdog`` aborts a stuck grow.
2. **cache-grow** — migrate live sessions' decode state: in place via
   ``core.grow_cache`` when the operator is LEMON-lossless (bit-exact),
   otherwise re-prefill each session's token history under the grown
   weights (exact by construction).
3. **swap** — ``engine.install`` flips the serving buffers between two
   decode steps.

Nothing touches the engine before stage 3, so any failure rolls back by
discarding buffers: the engine keeps decoding the old weights and zero
admitted requests are dropped. Failures retry (bounded, exponential
backoff); ``fail_at`` injects a one-shot chaos failure at a named stage
("grow" / "cache-grow" / "swap", or "hang" to wedge the grow thread and
exercise the watchdog) — one-shot so the retry demonstrates recovery.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional

import jax

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.grow_cache import (CacheGrowthError, can_grow_cache,
                                   depth_replay_plan, grow_decode_state,
                                   is_lossless_operator, replay_grow_state)
from repro.core.plan import place_operator, plan_for
from repro.serving.kv_pages import paged_supported

STAGES = ("grow", "cache-grow", "swap")


def _ledger_event(name: str, **attrs) -> None:
    """Mirror a hop lifecycle event into the attached compute ledger (if
    any), so the durable loss-vs-FLOPs record shows *where* the hops and
    rollbacks landed between the step records. No-op without a ledger."""
    led = obs.active_ledger()
    if led is not None:
        led.record_event(name, **attrs)


class HopError(RuntimeError):
    """A hop stage failed (injected or real); the hop rolls back."""


@dataclass
class HopWatchdog:
    """Deadline for the grow stage, tightened by what hops actually cost
    (the ``StragglerWatchdog`` idiom: an EWMA of observed durations sets the
    abort threshold, bounded by a hard ``timeout``).

    ``seed`` primes the EWMA *before the first hop* — from the background
    grow wall time measured at engine start (``HopController.warm``) or a
    config floor — and raises ``floor`` to that measurement. Previously the
    EWMA was seeded by the first grow itself, so a cold watchdog judged a
    slow first hop (which pays all the compiles) against the bare
    ``timeout``; the seeded floor now survives even a ``timeout`` set
    tighter than a real first grow costs.
    """
    timeout: float = 120.0
    mult: float = 5.0
    alpha: float = 0.5
    ewma: Optional[float] = None
    floor: float = 0.0

    def budget(self) -> float:
        if self.ewma is None:
            return max(self.floor, self.timeout)
        return max(self.floor,
                   min(self.timeout, max(0.05, self.mult * self.ewma)))

    def observe(self, dt: float) -> None:
        self.ewma = dt if self.ewma is None else (
            self.alpha * dt + (1 - self.alpha) * self.ewma)
        self.publish()

    def seed(self, dt: float) -> None:
        """Prime a cold watchdog with a measured (or configured) first-hop
        cost. No-op once real observations exist."""
        self.floor = max(self.floor, dt)
        if self.ewma is None:
            self.ewma = dt
        self.publish()

    def publish(self) -> None:
        """Expose EWMA/deadline/floor as obs gauges, so watchdog tuning is
        observable instead of inferred from timeouts."""
        if self.ewma is not None:
            obs.gauge("hop.watchdog.ewma_s").set(self.ewma)
        obs.gauge("hop.watchdog.budget_s").set(self.budget())
        obs.gauge("hop.watchdog.floor_s").set(self.floor)


class HopController:
    """Drives one live hop ``engine.cfg -> cfg2`` with operator ``ligo``.

    ``begin()`` launches the grow; the engine's step loop calls ``poll()``
    between decode steps, which advances the stage machine and performs
    cache migration + swap synchronously once the grown buffer is ready.
    ``cache_mode``: "auto" grows the cache in place iff the operator is
    provably lossless, replays only the new layers for a depth-only hop
    (when the engine kept the residual stream), else re-prefills;
    "grow"/"replay"/"reprefill" force a path.

    After a successful swap the pre-hop model is handed to the engine as a
    speculative-decoding drafter (``engine.adopt_drafter``) — its live
    decode state rides along, so drafting starts on the very next round.
    """

    def __init__(self, engine, cfg2: ModelConfig, ligo, *,
                 cache_mode: str = "auto", fail_at: Optional[str] = None,
                 retries: int = 2, backoff: float = 0.05,
                 timeout: float = 120.0, background: bool = True,
                 watchdog_floor: float = 0.0):
        assert cache_mode in ("auto", "grow", "replay", "reprefill"), \
            cache_mode
        assert fail_at in (None, "hang") + STAGES, fail_at
        self.engine = engine
        self.cfg2 = cfg2
        self.ligo = ligo
        self.cache_mode = cache_mode
        self.fail_at = fail_at
        self.retries = retries
        self.backoff = backoff
        self.background = background
        self.watchdog = HopWatchdog(timeout=timeout, floor=watchdog_floor)
        self.attempts = 0
        self.completed = False
        self.failed = False
        self.cache_path: Optional[str] = None
        self.swap_at_step: Optional[int] = None
        self.hop_ms: Optional[float] = None
        self._gen = 0
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._buf = None
        self._err: Optional[Exception] = None
        self._abort = threading.Event()
        self._retry_at: Optional[float] = None
        self._t_begin: Optional[float] = None
        self._t_launch: Optional[float] = None

    # -- chaos ---------------------------------------------------------------
    def _chaos(self, stage: str) -> None:
        if self.fail_at == stage:
            self.fail_at = None        # one-shot: the retry gets through
            raise HopError(f"injected failure at hop stage {stage!r}")

    # -- stage 1: grow (double-buffered, optionally backgrounded) -----------
    def _grow_once(self):
        eng = self.engine
        ligo = self.ligo
        plan = plan_for(eng.cfg, self.cfg2, eng.params)
        if eng.mesh is not None:
            # replicate the operator onto the mesh once, off the apply path
            ligo = place_operator(ligo, eng.mesh)
        grown = plan.executor(mesh=eng.mesh)(ligo, eng.params)
        jax.block_until_ready(grown)
        return grown

    def _stage_grow(self, abort: threading.Event):
        self._chaos("grow")
        if self.fail_at == "hang":     # wedge until the watchdog aborts us
            self.fail_at = None
            abort.wait()
            raise HopError("grow thread aborted by watchdog")
        return self._grow_once()

    def warm(self) -> float:
        """Run one synchronous grow at engine start — off the hop path,
        chaos-free, result discarded — and seed the watchdog with its wall
        time. This both pre-compiles the grow (the plan executor is
        memoised, so the real hop pays a dispatch) and fixes the cold-start
        bug: the first *live* hop is judged against a measured budget
        instead of a bare timeout it might legitimately exceed. The grown
        model's re-prefill (the cache migration's fallback) is compiled
        too, outside the watchdog's measure."""
        with obs.span("hop.warm", src=self.engine.cfg.name,
                      dst=self.cfg2.name) as sp:
            buf = self._grow_once()
        dt = sp.dur_ms / 1e3
        self.engine.warm_reprefill(buf, self.cfg2)
        del buf
        self.watchdog.seed(dt)
        print(f"[hop] warmed grow path in {dt * 1e3:.1f} ms "
              f"(watchdog seeded: budget {self.watchdog.budget():.2f}s)")
        return dt

    def _launch(self) -> None:
        self.attempts += 1
        self._gen += 1
        gen = self._gen
        self._buf, self._err = None, None
        self._retry_at = None
        self._abort = threading.Event()
        abort = self._abort
        self._t_launch = time.perf_counter()

        def grow_traced():
            # span opens in whichever thread runs the grow, so the dump
            # shows the background thread name next to the stage wall
            with obs.span("hop.grow", gen=gen, attempt=self.attempts):
                return self._stage_grow(abort)

        if not self.background:
            try:
                buf = grow_traced()
                with self._lock:
                    self._buf = buf
            except Exception as e:                     # noqa: BLE001
                with self._lock:
                    self._err = e
            return

        def run():
            try:
                buf = grow_traced()
                with self._lock:
                    if gen == self._gen:
                        self._buf = buf
            except Exception as e:                     # noqa: BLE001
                with self._lock:
                    if gen == self._gen:
                        self._err = e

        self._thread = threading.Thread(target=run, daemon=True,
                                        name=f"hop-grow-{gen}")
        self._thread.start()

    def begin(self) -> None:
        eng = self.engine
        print(f"[hop] beginning live hop {eng.cfg.name} -> {self.cfg2.name} "
              f"({'background' if self.background else 'synchronous'} grow, "
              f"{len(eng.live)} live sessions)")
        obs.event("hop.begin", src=eng.cfg.name, dst=self.cfg2.name,
                  live=len(eng.live), background=self.background)
        _ledger_event("hop.begin", src=eng.cfg.name, dst=self.cfg2.name,
                      live=len(eng.live))
        self._t_begin = time.perf_counter()
        self._launch()

    # -- stages 2+3, failure handling (engine thread) ------------------------
    def _fail(self, stage: str, err: Exception) -> None:
        eng = self.engine
        with self._lock:
            self._gen += 1             # orphan any in-flight grow thread
            self._buf, self._err = None, None
        self._abort.set()
        print(f"[hop] hop FAILED at stage={stage}: {err}; rolled back — "
              f"engine keeps serving {eng.cfg.name} "
              f"({len(eng.live)} in-flight sessions intact, 0 dropped)")
        obs.event("hop.rollback", stage=stage, cause=str(err),
                  attempt=self.attempts, gen=self._gen,
                  wall_s=round(time.perf_counter() - (self._t_begin or 0), 3),
                  live=len(eng.live), dropped=0)
        _ledger_event("hop.rollback", stage=stage, cause=str(err),
                      attempt=self.attempts, dropped=0)
        if self.attempts <= self.retries:
            delay = self.backoff * (2 ** (self.attempts - 1))
            self._retry_at = time.perf_counter() + delay
            print(f"[hop] retrying hop in {delay * 1e3:.0f} ms "
                  f"(attempt {self.attempts + 1}/{self.retries + 1})")
            obs.event("hop.retry", attempt=self.attempts + 1,
                      of=self.retries + 1, delay_ms=round(delay * 1e3, 1))
        else:
            self.failed = True
            print(f"[hop] giving up after {self.attempts} attempts; "
                  f"engine continues on {eng.cfg.name}")
            obs.event("hop.giveup", attempts=self.attempts)
        # every chaos path leaves a forensic trail (no-op without a dump dir)
        obs.flight_dump(f"hop-{stage}")

    def _migrate_state(self, grown):
        self._chaos("cache-grow")
        eng = self.engine
        if eng.kv_layout == "paged" and not paged_supported(self.cfg2):
            raise CacheGrowthError(
                f"{self.cfg2.name}: paged KV unsupported by the target "
                "architecture; serve with kv_layout='dense' to hop there")
        mode = self.cache_mode
        if mode == "auto":
            if (can_grow_cache(eng.cfg, self.cfg2)
                    and is_lossless_operator(self.ligo, eng.cfg, self.cfg2)):
                mode = "grow"
            elif (depth_replay_plan(self.ligo, eng.cfg, self.cfg2)
                    is not None and eng.replay_ready()):
                mode = "replay"
            else:
                mode = "reprefill"
        if mode == "grow":
            state = grow_decode_state(eng.state, self.ligo, eng.cfg,
                                      self.cfg2, mesh=eng.mesh)
        elif mode == "replay":
            if depth_replay_plan(self.ligo, eng.cfg, self.cfg2) is None:
                raise CacheGrowthError(
                    "cache_mode='replay': the operator is not a "
                    "depth-append (identity width + identity-prefix depth)")
            if not eng.replay_ready():
                raise CacheGrowthError(
                    "cache_mode='replay': the engine has no complete "
                    "residual stream for the live slots")
            state = replay_grow_state(eng.state, grown, eng.cfg, self.cfg2,
                                      eng.resid, mesh=eng.mesh)
        else:
            state = eng.reprefill_state(grown, self.cfg2)
        jax.block_until_ready(state)
        return state, mode

    def poll(self) -> bool:
        """Advance the hop between decode steps; True once settled
        (completed or given up)."""
        if self.completed or self.failed:
            return True
        if self._t_launch is None:     # begin() not called yet
            return False
        if self._retry_at is not None:
            if time.perf_counter() < self._retry_at:
                return False
            self._launch()
        with self._lock:
            buf, err = self._buf, self._err
        if err is not None:
            self._fail("grow", err)
            return self.failed
        if buf is None:
            elapsed = time.perf_counter() - self._t_launch
            if elapsed > self.watchdog.budget():
                obs.event("hop.watchdog_fire",
                          budget_s=round(self.watchdog.budget(), 3),
                          elapsed_s=round(elapsed, 3),
                          attempt=self.attempts)
                self._fail("grow", HopError(
                    f"watchdog: grow stage exceeded "
                    f"{self.watchdog.budget():.2f}s budget"))
            return self.failed
        self.watchdog.observe(time.perf_counter() - self._t_launch)
        eng = self.engine
        old_name = eng.cfg.name
        live = len(eng.live)
        try:
            with obs.span("hop.cache-grow", attempt=self.attempts,
                          live=live) as sp_cache:
                state, mode = self._migrate_state(buf)
                sp_cache.attrs["mode"] = mode
        except (HopError, CacheGrowthError) as e:
            self._fail("cache-grow", e)
            return self.failed
        old = (eng.cfg, eng.params, eng.state)
        try:
            with obs.span("hop.swap", attempt=self.attempts,
                          src=old_name, dst=self.cfg2.name):
                self._chaos("swap")
                eng.install(self.cfg2, buf, state)
        except HopError as e:
            self._fail("swap", e)
            return self.failed
        # the pre-hop model (with its live decode state) becomes the
        # speculative drafter — LiGO's premise in serving form: the small
        # model already approximates the grown one, for free
        drafting = eng.adopt_drafter(*old)
        self.completed = True
        self.cache_path = mode
        self.swap_at_step = eng.decode_steps
        self.hop_ms = (time.perf_counter() - self._t_begin) * 1e3
        obs.histogram("hop.total_ms").observe(self.hop_ms)
        obs.event("hop.complete", src=old_name, dst=self.cfg2.name,
                  hop_ms=round(self.hop_ms, 1), cache=mode, live=live,
                  attempt=self.attempts, of=self.retries + 1)
        _ledger_event("hop.complete", src=old_name, dst=self.cfg2.name,
                      cache=mode, attempt=self.attempts)
        wd = self.watchdog
        print(f"[hop] hop complete: {old_name} -> {self.cfg2.name} in "
              f"{self.hop_ms:.1f} ms (cache: {mode}, {live} live sessions "
              f"migrated, attempt {self.attempts}/{self.retries + 1}) | "
              f"watchdog ewma {wd.ewma:.2f}s budget {wd.budget():.2f}s "
              f"floor {wd.floor:.2f}s")
        if drafting:
            print(f"[spec] drafter resident: {old_name} drafts "
                  f"K={eng.spec_k} tokens/round for {self.cfg2.name} "
                  f"to verify")
        return True
