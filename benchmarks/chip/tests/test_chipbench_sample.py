"""``serve.sample_p95_ms``: the reader takes the 95th percentile of the
``serve.sample`` spans of the traced window and of no other span, gives no
number for a program without its spans on the trace's clock, and reads the
spans of a traced serving rehearsal, whose greedy rounds pick on the
device."""
import os
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chip.lib import harness as H  # noqa: E402
from benchmarks.chip.lib import program as P  # noqa: E402
from benchmarks.chip.lib import trace as T  # noqa: E402

import chipbench_tiny as tiny  # noqa: E402

NAME = "serve.sample_p95_ms"
MAIN = threading.current_thread().name


def _reader():
    return H.load_module("metrics", NAME)


def _span(name, start_us, dur_us):
    return P.Span(name, start_us * 1e3, (start_us + dur_us) * 1e3, MAIN,
                  name)


def test_reads_the_sample_spans_of_the_window(monkeypatch):
    # 20 rounds: samples of 1..20 us, decodes ten times longer, a prefill
    spans = [_span("serve.prefill", 0, 5000)]
    for k in range(20):
        spans += [_span("serve.decode", 1000 * k, 10 * (k + 1)),
                  _span("serve.sample", 1000 * k + 500, k + 1)]
    win = P.Window(spans=spans, compiles=[], idle={})
    monkeypatch.setattr(P, "window", lambda run: win)
    got = _reader().read(object())
    assert got == pytest.approx(P.p95([(k + 1) * 1e-3 for k in range(20)]))
    assert got == pytest.approx(19.05e-3)


def test_a_window_without_samples_reads_nothing(monkeypatch):
    win = P.Window(spans=[_span("serve.decode", 0, 10)], compiles=[],
                   idle={})
    monkeypatch.setattr(P, "window", lambda run: win)
    assert _reader().read(object()) is None


def test_reads_nothing_from_a_program_without_its_ring(monkeypatch):
    monkeypatch.setattr(P, "ring", lambda: None)

    class Run:
        trace, records = T.Trace(), {"setup_s": 1.0}
    assert _reader().read(Run()) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_traced_serving_rehearsal_reports_it(root):
    from repro import obs
    from repro.obs.trace import EPOCH
    picks = obs.counter_group("serve.picks")
    before = picks["device"], picks["host"]
    t0_ms = (time.perf_counter() - EPOCH) * 1e3
    res = tiny.run(root, "tiny.serve", trace=1)
    assert res["correct"]
    got = res["metrics"][NAME]
    assert got["unit"] == "ms" and got["value"] > 0
    # the mix is greedy: every served token was the program's pick
    assert picks["device"] > before[0] and picks["host"] == before[1]
    samples = [e for e in obs.FLIGHT.events(type="span",
                                            prefix="serve.sample")
               if e["t_ms"] >= t0_ms]             # this run's rounds only
    assert samples and {e["attrs"]["picked"] for e in samples} == {"device"}
