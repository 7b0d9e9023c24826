"""The compute ledger: durable loss-vs-FLOPs accounting, measured-vs-
modelled reconciliation, and the Perfetto timeline.

The contract under test:

- a :class:`repro.obs.ledger.RunLedger` is append-only JSONL whose cursor
  rides checkpoint meta — a trajectory killed mid-stage or mid-LiGO-phase
  and resumed produces a ledger record-for-record identical to the
  uninterrupted run (``wall_ms``/``run_id`` are the only intentionally
  non-deterministic fields);
- the compile-time measured-cost pass reconciles ``cost_analysis`` FLOPs
  (through the roofline trip-count correction) against the 6ND model
  within 2x for the train step and the LiGO scan chunk;
- ``savings_report`` reproduces the paper's headline metric — FLOPs to a
  target loss, grown run vs from-scratch baseline — with positive savings
  on a real proxy pair;
- the Chrome-trace exporter emits balanced B/E per tid, hop async spans,
  and the synthetic-clock ledger track.
"""
import json
import os
import tempfile
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs import costs
from repro.obs.ledger import (NONDETERMINISTIC_FIELDS, RunLedger,
                              attach_ledger, detach_ledger,
                              normalize_records, read_ledger, savings_report)
from repro.obs.metrics import MetricsRegistry
from repro.obs.timeline import export_chrome_trace, to_trace_events
from repro.configs.paper_models import BERT_SMALL
from repro.trajectory import (GrowthSpec, Stage, TrajectoryConfig,
                              TrajectoryRunner)
from test_trajectory import T0, T1, T2

# LiGO phase long enough to checkpoint mid-phase (ligo_fail_at=2 lands on
# the chunk boundary after the first 2-step scan chunk)
TRAJ_L = TrajectoryConfig(stages=(
    Stage(T0, 5),
    Stage(T1, 5, GrowthSpec(method="ligo", ligo_steps=4, ligo_scan_chunk=2)),
    Stage(T2, 5, GrowthSpec(method="stackbert"))),
    batch=4, seq=16, lr=1e-3, checkpoint_every=3)

TINY = BERT_SMALL.scaled(name="led-tiny", n_layers=2, d_model=32, n_heads=4,
                         n_kv_heads=4, d_head=8, d_ff=64, vocab_size=64,
                         max_seq=64, dtype="float32", objective="clm",
                         encoder_only=False, causal=True)
BIG = TINY.scaled(name="led-big", n_layers=4, d_model=48, d_head=12, d_ff=96)


def _assert_balanced(events):
    """Every ph:"B" has a matching ph:"E" on the same tid (the CI timeline
    gate); returns per-(tid, name) open counts for extra assertions."""
    opens = {}
    for e in events:
        if e["ph"] == "B":
            opens[(e["tid"], e["name"])] = opens.get(
                (e["tid"], e["name"]), 0) + 1
        elif e["ph"] == "E":
            opens[(e["tid"], e["name"])] = opens.get(
                (e["tid"], e["name"]), 0) - 1
    assert all(v == 0 for v in opens.values()), opens
    return opens


# ---------------------------------------------------------------------------
# RunLedger durability mechanics
# ---------------------------------------------------------------------------
def test_ledger_snapshot_restore_truncates_to_cursor(tmp_path):
    """Records appended after the checkpointed cursor — including a torn
    partial line from a mid-write kill — are discarded on restore, and
    re-appending the same records reproduces the file byte-for-byte."""
    path = str(tmp_path / "run.jsonl")

    def emit(led, lo, hi):
        for i in range(lo, hi):
            led.record_step(stage=0, arch="a", step=i, loss=4.0 - 0.1 * i,
                            tokens=64.0, wall_ms=1.0 + i,
                            flops_modelled=100.0, flops_measured=90.0)

    led = RunLedger(path, run_id="r")
    led.restore(None)
    emit(led, 0, 3)
    cursor = led.snapshot()
    assert cursor["n_records"] == 3
    assert cursor["cum_flops_modelled"] == pytest.approx(300.0)
    assert cursor["cum_flops_measured"] == pytest.approx(270.0)
    emit(led, 3, 5)                       # post-checkpoint tail
    led.record_event("hop.begin", stage=1, step=5, src="a", dst="b")
    led.close()
    with open(path, "ab") as fh:          # torn line from a mid-write kill
        fh.write(b'{"type": "step", "par')
    want = []
    for r in read_ledger(path)[:3]:
        want.append(r)

    led2 = RunLedger(path)
    led2.restore(cursor)
    assert led2.run_id == "r"             # cursor carries the run identity
    assert os.path.getsize(path) == cursor["byte_offset"]
    emit(led2, 3, 5)                      # deterministic re-execution
    led2.close()
    recs = read_ledger(path)
    assert len(recs) == 5
    assert recs[:3] == want
    assert [r["step"] for r in recs] == [0, 1, 2, 3, 4]
    cm = [r["cum_flops_modelled"] for r in recs]
    assert cm == sorted(cm) and cm[-1] == pytest.approx(500.0)

    # wall_ms differs between runs by design; normalize masks exactly that
    norm = normalize_records(recs)
    assert all(f not in r for r in norm for f in NONDETERMINISTIC_FIELDS)


def test_ledger_restore_rejects_missing_bytes(tmp_path):
    path = str(tmp_path / "run.jsonl")
    led = RunLedger(path)
    led.restore(None)
    led.record_step(stage=0, arch="a", step=0, loss=1.0, tokens=1.0,
                    wall_ms=0.0, flops_modelled=1.0)
    cursor = led.snapshot()
    led.close()
    os.truncate(path, cursor["byte_offset"] // 2)
    with pytest.raises(ValueError, match="truncated"):
        RunLedger(path).restore(cursor)


def test_read_ledger_skips_torn_tail(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with open(path, "w") as fh:
        fh.write('{"type": "step", "step": 0}\n{"type": "st')
    recs = read_ledger(path)
    assert len(recs) == 1 and recs[0]["step"] == 0


def test_attach_ledger_is_exclusive(tmp_path):
    led = attach_ledger(str(tmp_path / "a.jsonl"))
    try:
        assert obs.active_ledger() is led
        with pytest.raises(RuntimeError, match="already attached"):
            attach_ledger(str(tmp_path / "b.jsonl"))
    finally:
        assert detach_ledger() is led
    assert obs.active_ledger() is None


# ---------------------------------------------------------------------------
# savings_report
# ---------------------------------------------------------------------------
def _synthetic_ledger(flops_per_step, losses, *, measured=False):
    led = []
    cum = 0.0
    for i, (f, l) in enumerate(zip(flops_per_step, losses)):
        cum += f
        led.append({"type": "step", "step": i, "stage": 0, "arch": "x",
                    "loss": l, "cum_flops_modelled": cum,
                    "cum_flops_measured": cum * 0.9,
                    "measured": measured})
    return led


def test_savings_report_synthetic():
    run = _synthetic_ledger([1.0] * 5, [5.0, 4.0, 3.0, 2.0, 1.0])
    base = _synthetic_ledger([2.0] * 5, [5.0, 4.0, 3.0, 2.0, 1.0])
    rep = savings_report(3.0, run, baseline=base)
    assert rep["basis"] == "modelled"
    assert rep["run"]["flops"] == pytest.approx(3.0)
    assert rep["baseline"]["flops"] == pytest.approx(6.0)
    assert rep["savings_frac"] == pytest.approx(0.5)
    assert not rep["censored_baseline"]

    # measured basis only when BOTH crossings carry measured numbers
    rep_m = savings_report(
        3.0, _synthetic_ledger([1.0] * 5, [5, 4, 3, 2, 1], measured=True),
        baseline=_synthetic_ledger([2.0] * 5, [5, 4, 3, 2, 1],
                                   measured=True))
    assert rep_m["basis"] == "measured"
    rep_mix = savings_report(
        3.0, _synthetic_ledger([1.0] * 5, [5, 4, 3, 2, 1], measured=True),
        baseline=base)
    assert rep_mix["basis"] == "modelled"

    # baseline that never reaches the target: censored lower bound
    rep_c = savings_report(
        1.0, run, baseline=_synthetic_ledger([2.0] * 3, [5.0, 4.5, 4.0]))
    assert rep_c["censored_baseline"]
    assert not rep_c["baseline"]["reached"]
    assert rep_c["savings_flops"] == pytest.approx(6.0 - 5.0)

    # the run itself must reach the target
    with pytest.raises(ValueError, match="never reached"):
        savings_report(0.5, run, baseline=base)


# ---------------------------------------------------------------------------
# /metrics endpoint
# ---------------------------------------------------------------------------
def test_serve_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("led.scrapes").inc(3)
    h = reg.histogram("led.lat_ms", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    server = obs.serve_metrics(0, registry=reg)
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode("utf-8")
        assert "led_scrapes_total 3" in body
        # histogram buckets are cumulative, +Inf holds the total count
        assert 'led_lat_ms_bucket{le="2"} 2' in body
        assert 'led_lat_ms_bucket{le="+Inf"} 4' in body
        assert "led_lat_ms_count 4" in body
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/other",
                                   timeout=10)
        assert ei.value.code == 404
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# Telemetry: the measured-FLOPs switch keeps replay determinism
# ---------------------------------------------------------------------------
def test_telemetry_set_flops_per_step_resume_deterministic():
    from repro.autogrow.telemetry import Telemetry
    losses = [4.0 - 0.05 * i for i in range(12)]
    a = Telemetry(window=4, flops_per_step=100.0)
    a.set_flops_per_step(90.0)            # the measured number, pre-step-0
    for i, l in enumerate(losses):
        a.record(i, l)

    b = Telemetry(window=4, flops_per_step=100.0)
    b.set_flops_per_step(90.0)
    for i, l in enumerate(losses[:7]):
        b.record(i, l)
    snap = b.snapshot()
    assert snap["cum_flops"] == pytest.approx(7 * 90.0)
    # resumed process re-measures the same compiled program -> same number
    c = Telemetry.restore(snap, flops_per_step=90.0)
    for i, l in enumerate(losses[7:], start=7):
        c.record(i, l)
    assert c.snapshot() == a.snapshot()
    assert c.rpf() == pytest.approx(a.rpf())


# ---------------------------------------------------------------------------
# The trajectory contract: one uninterrupted reference run, then kills
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def uninterrupted(tmp_path_factory):
    d = tmp_path_factory.mktemp("ledger_ref")
    path = str(d / "ref.jsonl")
    led = RunLedger(path, run_id="ref")
    res = TrajectoryRunner(TRAJ_L, ckpt_dir=str(d / "ck"), verbose=False,
                           ledger=led).run()
    led.close()
    assert res["status"] == "done"
    keys = ("train_step[tr0]", "ligo_chunk[tr1]", "train_step[tr1]",
            "train_step[tr2]")
    meas = {k: dict(costs.measurement(k)) for k in keys
            if costs.measurement(k) is not None}
    return {"records": read_ledger(path), "measurements": meas}


def test_ledger_records_cover_the_whole_run(uninterrupted):
    recs = uninterrupted["records"]
    steps = [r for r in recs if r["type"] == "step"]
    events = [r for r in recs if r["type"] == "event"]
    assert len(steps) == 15 + 4           # 3x5 train + 4 LiGO-phase steps
    assert {r["phase"] for r in steps} == {"train", "ligo"}
    assert [r["arch"] for r in steps if r["phase"] == "train"] \
        == ["tr0"] * 5 + ["tr1"] * 5 + ["tr2"] * 5
    cm = [r["cum_flops_modelled"] for r in steps]
    assert all(b > a for a, b in zip(cm, cm[1:])), "cum FLOPs not monotone"
    cms = [r["cum_flops_measured"] for r in steps]
    assert all(b > a for a, b in zip(cms, cms[1:]))
    assert all(r["measured"] for r in steps)
    names = [e["name"] for e in events]
    assert names.count("hop.begin") == 2 and names.count("hop.complete") == 2
    # hop.begin records the architecture transition
    hops = [e for e in events if e["name"] == "hop.begin"]
    assert (hops[0]["attrs"]["src"], hops[0]["attrs"]["dst"]) == ("tr0",
                                                                  "tr1")
    assert (hops[1]["attrs"]["src"], hops[1]["attrs"]["dst"]) == ("tr1",
                                                                  "tr2")


def test_measured_vs_modelled_reconciles_within_2x(uninterrupted):
    """Acceptance: the compile-time measured FLOPs agree with the 6ND
    model within [0.5, 2.0] for the train step AND the LiGO scan chunk
    (the trip-count correction is what keeps the chunk in range — raw
    cost_analysis counts the scan body once)."""
    meas = uninterrupted["measurements"]
    for key in ("train_step[tr0]", "ligo_chunk[tr1]", "train_step[tr1]",
                "train_step[tr2]"):
        m = meas.get(key)
        assert m is not None, f"no measurement recorded for {key}"
        assert m["flops"] > 0 and m["modelled_flops"] > 0
        assert 0.5 <= m["ratio"] <= 2.0, (key, m["ratio"])
    # the scan correction actually fired on the chunked LiGO program
    assert meas["ligo_chunk[tr1]"]["trip_annotations"] >= 1


def test_measure_failure_is_counted_not_hidden(capsys):
    """A program the pass cannot compile is counted per program name and
    reported, and the caller gets ``None`` back to run unmeasured."""
    fails = obs.counter_group("ledger.measure.failures")
    before = fails["broken[x]"]

    def broken(x):
        raise ValueError("cannot trace this")

    assert costs.measure_jitted("broken[x]", jax.jit(broken),
                                np.ones(2, np.float32)) is None
    assert fails["broken[x]"] == before + 1
    assert "broken[x] failed" in capsys.readouterr().err
    assert costs.measurement("broken[x]") is None


def test_kill_mid_stage_resumes_record_identical(tmp_path):
    """Acceptance: kill the 3-stage trajectory mid-stage (global step 8 =
    stage 1 step 3), resume, and the final ledger is record-for-record
    identical to the uninterrupted run's (wall_ms/run_id masked)."""
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    ref_path = str(ref_dir / "a.jsonl")
    la = RunLedger(ref_path, run_id="a")
    TrajectoryRunner(TRAJ_L, ckpt_dir=str(ref_dir / "ck"), verbose=False,
                     ledger=la).run()
    la.close()

    path = str(tmp_path / "b.jsonl")
    ck = str(tmp_path / "ck")
    lb = RunLedger(path, run_id="b")
    r1 = TrajectoryRunner(TRAJ_L, ckpt_dir=ck, verbose=False,
                          ledger=lb).run(max_steps=8)
    assert r1["status"] == "paused"
    assert (r1["stage"], r1["stage_step"]) == (1, 3)
    lb.close()

    lb2 = RunLedger(path, run_id="b2")    # fresh process: new ledger object
    r2 = TrajectoryRunner(TRAJ_L, ckpt_dir=ck, verbose=False,
                          ledger=lb2).run()
    assert r2["status"] == "done" and r2["resumed_at"] == (1, 3)
    lb2.close()

    na = normalize_records(read_ledger(ref_path))
    nb = normalize_records(read_ledger(path))
    assert na == nb


def test_kill_mid_ligo_phase_resumes_record_identical(tmp_path):
    """Same contract through the harder kill point: inside the LiGO phase
    (after the phase checkpoint at step 2 of 4). The resumed phase replays
    its pre-kill step records from the checkpointed losses (wall_ms=0) and
    re-runs the rest, so the ledger stays record-identical."""
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    ref_path = str(ref_dir / "a.jsonl")
    la = RunLedger(ref_path, run_id="a")
    TrajectoryRunner(TRAJ_L, ckpt_dir=str(ref_dir / "ck"), verbose=False,
                     ledger=la).run()
    la.close()

    path = str(tmp_path / "b.jsonl")
    ck = str(tmp_path / "ck")
    lb = RunLedger(path, run_id="b")
    r1 = TrajectoryRunner(TRAJ_L, ckpt_dir=ck, verbose=False, ledger=lb,
                          ligo_fail_at=2)
    with pytest.raises(RuntimeError, match="LiGO"):
        r1.run()
    lb.close()

    lb2 = RunLedger(path, run_id="b2")
    r2 = TrajectoryRunner(TRAJ_L, ckpt_dir=ck, verbose=False,
                          ledger=lb2).run()
    assert r2["status"] == "done"
    lb2.close()

    na = normalize_records(read_ledger(ref_path))
    nb = normalize_records(read_ledger(path))
    assert na == nb
    # the replayed LiGO records carry the sentinel wall (not re-measured)
    ligo_b = [r for r in read_ledger(path)
              if r["type"] == "step" and r["phase"] == "ligo"]
    assert len(ligo_b) == 4
    assert any(r["wall_ms"] == 0.0 for r in ligo_b[:2])


def test_savings_report_on_grown_vs_scratch_proxy_pair(tmp_path):
    """Acceptance: the paper's headline metric on a real (proxy-scale)
    pair — grow tr0→tr1 vs train tr1 from scratch on the same data — shows
    positive FLOPs savings to the loss level the cheap small stage buys."""
    grown_cfg = TrajectoryConfig(stages=(
        Stage(T0, 30),
        Stage(T1, 30, GrowthSpec(method="ligo", ligo_steps=2))),
        batch=4, seq=16, lr=1e-3, checkpoint_every=100)
    scratch_cfg = TrajectoryConfig(stages=(Stage(T1, 60),),
                                   batch=4, seq=16, lr=1e-3,
                                   checkpoint_every=100)
    pg, ps = str(tmp_path / "g.jsonl"), str(tmp_path / "s.jsonl")
    lg = RunLedger(pg, run_id="grown")
    TrajectoryRunner(grown_cfg, ckpt_dir=str(tmp_path / "ckg"),
                     verbose=False, ledger=lg).run()
    lg.close()
    ls = RunLedger(ps, run_id="scratch")
    TrajectoryRunner(scratch_cfg, ckpt_dir=str(tmp_path / "cks"),
                     verbose=False, ledger=ls).run()
    ls.close()

    grown = read_ledger(pg)
    target = min(r["loss"] for r in grown
                 if r["type"] == "step" and r["stage"] == 0)
    rep = savings_report(target, pg, baseline=ps)
    assert rep["basis"] == "measured"     # both lanes ran the cost pass
    assert rep["run"]["flops"] > 0 and rep["baseline"]["flops"] > 0
    assert rep["savings_flops"] > 0
    assert rep["savings_frac"] > 0.1, rep
    # reported crossing is a real record of the grown run
    assert rep["run"]["arch"] in ("tr0", "tr1")


# ---------------------------------------------------------------------------
# Serving side: hop events + measured decode through the active ledger
# ---------------------------------------------------------------------------
def test_live_hop_chaos_events_land_in_ledger(tmp_path):
    """A real hop with an injected cache-grow failure mirrors its whole
    lifecycle (begin → rollback → retry → complete) into the attached
    ledger, and engine.install runs the measured decode-step pass."""
    from repro.core import init_ligo_params
    from repro.models.model import init_params
    from repro.serving import HopController, ServingEngine

    led = attach_ledger(str(tmp_path / "hop.jsonl"))
    try:
        led.restore(None)
        params = init_params(TINY, jax.random.PRNGKey(0))
        eng = ServingEngine(params, TINY, slots=2, prompt_budget=8,
                            gen_budget=8)
        m = costs.measurement(f"decode_step[{TINY.name}]")
        assert m is not None and m["flops"] > 0
        assert m["per_call_units"] == 2.0    # per-token FLOPs basis
        assert m["flops_per_unit"] == pytest.approx(m["flops"] / 2.0)
        for _ in range(2):
            eng.submit([1, 2, 3], max_new=4)
        op = init_ligo_params(jax.random.PRNGKey(1), TINY, BIG)
        hop = HopController(eng, BIG, op, fail_at="cache-grow", retries=2,
                            backoff=0.01, background=False)
        hop.begin()
        while not hop.poll():
            pass
        assert hop.completed
        led.snapshot()
        names = [r["name"] for r in read_ledger(led.path)
                 if r["type"] == "event"]
        assert names[0] == "hop.begin"
        assert "hop.rollback" in names
        assert names[-1] == "hop.complete"
        # the post-swap install measured the grown decode step too
        assert costs.measurement(f"decode_step[{BIG.name}]") is not None
    finally:
        detach_ledger()


# ---------------------------------------------------------------------------
# Timeline export
# ---------------------------------------------------------------------------
def test_to_trace_events_nesting_async_and_ledger_track():
    records = [
        {"type": "span", "name": "traj.train", "t_ms": 0.0, "dur_ms": 10.0,
         "thread": "MainThread", "attrs": {"stage": 0}},
        # child whose recorded end drifts past its parent's: clamped inside
        {"type": "span", "name": "ligo.chunk", "t_ms": 2.0, "dur_ms": 12.0,
         "thread": "MainThread", "attrs": {}},
        {"type": "span", "name": "hop.grow", "t_ms": 20.0, "dur_ms": 5.0,
         "thread": "hop-grow-1", "attrs": {"gen": 3}},
        {"type": "event", "name": "hop.watchdog_fire", "t_ms": 21.0,
         "thread": "MainThread", "attrs": {"budget_s": 1.0}},
    ]
    ledger_records = [
        {"type": "step", "wall_ms": 1.5, "loss": 4.0,
         "cum_flops_modelled": 10.0, "cum_flops_measured": 12.0},
        {"type": "event", "name": "hop.begin", "attrs": {"src": "a"}},
        {"type": "step", "wall_ms": 2.5, "loss": 3.5,
         "cum_flops_modelled": 20.0, "cum_flops_measured": 24.0},
    ]
    ev = to_trace_events(records, pid=7, ledger_records=ledger_records)
    _assert_balanced(ev)
    assert all(e["pid"] == 7 for e in ev)

    # nesting: the drifting child's E lands at (not past) its parent's end
    e_ts = {(x["name"]): x["ts"] for x in ev if x["ph"] == "E"}
    assert e_ts["ligo.chunk"] <= e_ts["traj.train"] == 10_000.0

    # hop spans double as async pairs keyed by generation
    bs = [x for x in ev if x["ph"] == "b"]
    es = [x for x in ev if x["ph"] == "e"]
    assert [x["name"] for x in bs] == ["hop.grow"]
    assert bs[0]["id"] == "3" and es[0]["id"] == "3"

    # point events become instants
    assert any(x["ph"] == "i" and x["name"] == "hop.watchdog_fire"
               for x in ev)

    # ledger track: synthetic clock = cumulative wall_ms, counters + instants
    cs = [x for x in ev if x["ph"] == "C"]
    assert {x["name"] for x in cs} == {"ledger.loss", "ledger.cum_flops"}
    loss_ts = [x["ts"] for x in cs if x["name"] == "ledger.loss"]
    assert loss_ts == [1500.0, 4000.0]
    led_i = [x for x in ev if x["ph"] == "i" and x["name"] == "hop.begin"]
    assert led_i and led_i[0]["ts"] == 1500.0  # between the two steps

    # thread metadata names every tid (plus the ledger track)
    tid_names = {x["tid"]: x["args"]["name"] for x in ev
                 if x["ph"] == "M" and x["name"] == "thread_name"}
    assert "MainThread" in tid_names.values()
    assert "hop-grow-1" in tid_names.values()
    assert any("ledger" in v for v in tid_names.values())


def test_export_chrome_trace_is_valid_and_balanced(tmp_path,
                                                   uninterrupted):
    """export_chrome_trace on the live flight ring + a real run ledger
    loads back as valid trace-event JSON with balanced B/E per tid."""
    led_path = str(tmp_path / "run.jsonl")
    with open(led_path, "w") as fh:
        for r in uninterrupted["records"]:
            fh.write(json.dumps(r) + "\n")
    out = str(tmp_path / "trace.json")
    export_chrome_trace(out, ledger=led_path)
    trace = json.load(open(out))
    assert set(trace) >= {"traceEvents", "displayTimeUnit"}
    ev = trace["traceEvents"]
    _assert_balanced(ev)
    # the ledger track carries one loss counter per step record
    n_steps = sum(1 for r in uninterrupted["records"]
                  if r["type"] == "step")
    assert sum(1 for x in ev
               if x["ph"] == "C" and x["name"] == "ledger.loss") == n_steps


def test_timeline_cli_roundtrip(tmp_path):
    """python -m repro.obs.timeline converts an obs JSONL to a loadable
    trace."""
    from repro.obs.timeline import _main
    src = str(tmp_path / "obs.jsonl")
    with open(src, "w") as fh:
        fh.write(json.dumps({"type": "span", "name": "hop.grow",
                             "t_ms": 0.0, "dur_ms": 2.0,
                             "thread": "w", "attrs": {"gen": 1}}) + "\n")
        fh.write("{torn")
    out = str(tmp_path / "trace.json")
    _main([src, "-o", out])
    trace = json.load(open(out))
    _assert_balanced(trace["traceEvents"])
    assert any(x["ph"] == "b" for x in trace["traceEvents"])
