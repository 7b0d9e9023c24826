"""The program's own spans and compiles on the trace's clock: the offset
found from the harness's spans, the idle split by program span on a
hand-built trace, the same offset on a trace recorded on the CPU, the
harness's own reduction left as it was, and traced rehearsals of the LiGO
and serving cells that print every metric read from the program."""
import glob
import os
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chip.lib import program as P  # noqa: E402
from benchmarks.chip.lib import trace as T  # noqa: E402

import chipbench_tiny as tiny  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "cpu_window.xplane.pb")
US = 1e3                                   # ns in a microsecond
MAIN = threading.current_thread().name

# The window spans 0..100 us; device ops 0..10 and 60..100, so one gap,
# 10..60. Program spans: grow 5..55 with its child grow/ligo.chunk 20..40
# on the window's thread, hop.grow 0..100 on another thread. Pieces of the
# gap: 10..20 grow, 20..40 grow/ligo.chunk, 40..55 grow (the window's
# thread before the other), 55..60 hop.grow.
PROTO = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 60000000 duration_ps: 40000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.A" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def _span(name, a, b, path=None, thread=MAIN):
    return P.Span(name, a * US, b * US, thread, path or name)


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return T.load(ProfileData.from_text_proto(PROTO))


def test_idle_split_across_nested_spans(hand):
    spans = [_span("grow", 5, 55),
             _span("ligo.chunk", 20, 40, "grow/ligo.chunk"),
             _span("hop.grow", 0, 100, thread="hop-grow-1")]
    idle = P.idle_by_program_span(hand, spans, MAIN)
    assert idle["grow"] == pytest.approx(25e-6)
    assert idle["grow/ligo.chunk"] == pytest.approx(20e-6)
    assert idle["hop.grow"] == pytest.approx(5e-6)
    assert sum(idle.values()) == pytest.approx(
        T.summarize(hand).window_s - T.summarize(hand).busy_s)
    win = P.Window(spans=spans, compiles=[], idle=idle)
    assert win.idle_within("ligo.chunk") == pytest.approx(20e-6)
    assert win.idle_within("grow", outside=("ligo.chunk",)) == \
        pytest.approx(25e-6)


def test_idle_outside_every_span_is_none(hand):
    idle = P.idle_by_program_span(hand, [_span("grow", 30, 40)], MAIN)
    assert idle == {"grow": pytest.approx(10e-6),
                    "none": pytest.approx(40e-6)}


def test_align_finds_the_offset_from_nesting():
    # harness spans every 170 ms, each 150 ms long; the program's outermost
    # span starts 30 to 90 us after its harness span and ends before it
    true = 5e12
    host = [T.Event("bench.engine_step", k * 170e6, k * 170e6 + 150e6)
            for k in range(40)]
    delays = [30e3 + (k * 7919) % 60e3 for k in range(40)]
    outer = [(k * 170e6 + d - true, k * 170e6 + d + 100e6 - true)
             for k, d in enumerate(delays)]
    # a first guess 40 ms off, and one 120 ms off with a wider search,
    # which also reaches an alignment one harness span off: it holds one
    # span fewer, so the true one wins
    for first, search in ((true + 40e6, 0.1e9), (true - 120e6, 0.3e9)):
        got = P.align(outer, host, first, search)
        assert got == pytest.approx(true - min(delays), abs=1.0)
    # nothing inside anything: the first guess stands
    assert P.align([(0.0, 1e12)], host, 7.0) == 7.0


def test_recorded_trace_spans_land_on_their_annotations(tmp_path):
    """On a real trace: the ring's spans, moved by the offset found from
    the harness's spans, fall where the profiler put their annotations."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from repro import obs
    from repro.obs.trace import EPOCH, FLIGHT

    x = jnp.ones((128, 128))
    jax.block_until_ready(x @ x)
    t_window = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            for k in range(6):
                with jax.profiler.TraceAnnotation("bench.step"):
                    with obs.span("serve.decode", k=k):
                        jax.block_until_ready(x @ x)
                    with obs.span("serve.sample", k=k):
                        time.sleep(0.002)
                time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    tr = T.load(path, **T.CPU_LINES)
    recs = [r for r in FLIGHT.events(type="span")
            if r["name"] in ("serve.decode", "serve.sample")][-12:]
    outer = [(r["t_ms"] * 1e6, (r["t_ms"] + r["dur_ms"]) * 1e6)
             for r in recs]
    host = [e for e in tr.host if e.name != T.WINDOW_SPAN]
    off = P.align(outer, host, (EPOCH - t_window) * 1e9)
    truth = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    truth[(e.name, dict(e.stats)["k"])] = e.start_ns
    assert len(truth) == 12
    for r in recs:
        want = truth[(r["name"], r["attrs"]["k"])]
        assert abs(r["t_ms"] * 1e6 + off - want) < 1e6     # within 1 ms
    obs.REGISTRY.reset()


def test_fixture_summary_unchanged():
    s = T.summarize(T.load(FIXTURE, **T.CPU_LINES))
    assert s.busy_s == pytest.approx(0.00038959, rel=1e-9)
    assert s.window_s == pytest.approx(0.00758921, rel=1e-9)
    assert s.idle_by_span == {
        "bench.step": pytest.approx(0.000298922, rel=1e-9),
        "bench.host_work": pytest.approx(0.006900698, rel=1e-9)}
    b = s.breakdown()
    assert [k for k, _ in b["device_ops"]] == [
        "dot_general.1", "wrapped_reduce-window", "wrapped_tanh",
        "wrapped_reduce", "end: dot_general.1", "end: wrapped_reduce-window",
        "end: wrapped_tanh", "end: wrapped_reduce",
        "ThunkExecutor::Execute (wait for completion)"]
    assert b["device_ops"][0][1] == pytest.approx(0.000313077, rel=1e-9)
    assert b["idle_gaps"] == [
        ["bench.host_work", pytest.approx(0.006900698, rel=1e-9)],
        ["bench.step", pytest.approx(0.000298922, rel=1e-9)]]


NEW = {"tiny.ligo": {"hop.compile_s", "ligo.traces", "ligo.chunk_idle_s",
                     "grow.idle_s"},
       "tiny.serve": {"serve.prefill_p95_ms", "serve.queue_wait_p95_ms",
                      "hop.engine_block_ms"}}


def test_readers_read_nothing_from_an_older_program(monkeypatch):
    """A program without these records (the parent of this change, say)
    gives no number, and no reader raises."""
    from benchmarks.chip.lib import harness as H
    monkeypatch.setattr(P, "ring", lambda: None)

    class Run:
        trace, records = T.Trace(), {"setup_s": 1.0, "hops": 2}
    for name in set().union(*NEW.values()):
        assert H.load_module("metrics", name).read(Run()) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_rehearsal_prints_program_metrics(root, cell):
    res = tiny.run(root, cell, trace=1)
    assert res["correct"]
    got = res["metrics"]
    assert NEW[cell] <= set(got)
    for name in NEW[cell]:
        assert got[name]["value"] >= 0
    if cell == "tiny.ligo":
        # every idle second of the window falls in one of the two
        idle_per_hop = ((res["device"]["window_s"] - res["device"]["busy_s"])
                        / res["attempted"])
        split = got["ligo.chunk_idle_s"]["value"] + got["grow.idle_s"]["value"]
        assert split == pytest.approx(idle_per_hop, rel=0.05)
    else:
        assert got["hop.engine_block_ms"]["value"] > 0
