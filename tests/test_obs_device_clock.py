"""obs spans on the device trace's clock, compiles counted by span, and the
serving engine's and the LiGO phase's spans at the host's boundaries.

A span enters a ``jax.profiler.TraceAnnotation`` for its lifetime, so a
profiled window holds the program's spans (with their scalar attrs as the
event's arguments) on the same timeline as the harness's annotations and
the device ops; a disabled span writes nothing there.
"""
import glob
import os
import subprocess
import sys
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs.paper_models import BERT_SMALL
from repro.core.grow import grow
from repro.models import init_params
from repro.models.inputs import dummy_batch
from repro.obs.trace import EPOCH, FLIGHT
from repro.serving import ServingEngine

TINY = BERT_SMALL.scaled(
    name="clk-tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
    d_head=8, d_ff=64, vocab_size=64, max_seq=64, dtype="float32",
    objective="clm", encoder_only=False, causal=True)
BIG = TINY.scaled(name="clk-big", n_layers=4, d_model=48, d_head=12,
                  d_ff=96)
SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.set_enabled(True)
    FLIGHT.clear()
    obs.REGISTRY.reset()
    yield
    obs.set_enabled(True)
    FLIGHT.clear()
    obs.REGISTRY.reset()


def _events(log_dir):
    """name -> [(start_ns, end_ns, stats)] of every event in the trace."""
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _fresh(n):
    """A program no cache has seen: the constant makes its HLO new."""
    c = float(uuid.uuid4().int % 100000)
    return jax.jit(lambda x: jnp.tanh(x * c) + c)(jnp.ones((n,)))


def test_span_reaches_profiler_trace_with_args(tmp_path):
    jax.block_until_ready(jnp.ones((4,)) * 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with obs.span("ligo.chunk", start=0, n=3, uid=7, mode="x",
                          skip=[1, 2]):
                jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
            obs.set_enabled(False)
            with obs.span("invisible", n=1):
                pass
            obs.set_enabled(True)
    finally:
        jax.profiler.stop_trace()
    evs = _events(str(tmp_path))
    assert "invisible" not in evs
    ((w0, w1, _),) = evs["bench.window"]
    ((s0, s1, stats),) = evs["ligo.chunk"]
    # the program's span and the harness's share one timeline
    assert w0 <= s0 <= s1 <= w1
    # scalar attrs become the event's arguments; a list does not
    assert stats == {"start": 0, "n": 3, "uid": 7, "mode": "x"}
    # the ring keeps its record as before
    (rec,) = FLIGHT.events(type="span", prefix="ligo.chunk")
    assert rec["attrs"]["skip"] == [1, 2]
    assert set(rec) == {"type", "name", "span_id", "parent_id", "thread",
                        "t_ms", "dur_ms", "attrs"}


def test_disabled_span_still_times_its_block():
    obs.set_enabled(False)
    with obs.span("off") as sp:
        pass
    assert sp.dur_ms is not None and sp.dur_ms >= 0
    assert FLIGHT.events() == []


def test_compiles_counted_by_innermost_span():
    compiles = obs.counter_group("jax.compiles")
    secs = obs.counter_group("jax.compile_s")
    with obs.span("outer"):
        with obs.span("inner"):
            _fresh(17)
        _fresh(19)
    _fresh(23)
    assert compiles["inner"] >= 1 and secs["inner"] > 0
    assert compiles["outer"] >= 1 and secs["outer"] > 0
    assert compiles["none"] >= 1
    where = [e["attrs"]["span"] for e in FLIGHT.events(type="event")
             if e["name"] == "jax.compile"]
    assert {"inner", "outer", "none"} <= set(where)
    assert sum(compiles.get(k) for k in compiles) == len(where)


def test_obs_imports_and_spans_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro import obs\n"
            "with obs.span('serve.prefill', uid=1) as sp:\n"
            "    pass\n"
            "assert sp.dur_ms >= 0\n"
            "assert obs.FLIGHT.events()[-1]['name'] == 'serve.prefill'\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


@pytest.fixture(scope="module")
def small_params():
    return init_params(TINY, jax.random.PRNGKey(0))


def test_prefill_span_ends_after_first_token(small_params):
    eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                        gen_budget=6)
    rng = np.random.RandomState(0)
    reqs = [eng.submit(list(rng.randint(0, TINY.vocab_size, 4 + i)),
                       max_new=6) for i in range(3)]
    eng.run()
    spans = {e["attrs"]["uid"]: e
             for e in FLIGHT.events(type="span", prefix="serve.prefill")}
    assert set(spans) == {r.uid for r in reqs}
    for r in reqs:
        e = spans[r.uid]
        start = EPOCH + e["t_ms"] / 1e3
        end = EPOCH + (e["t_ms"] + e["dur_ms"]) / 1e3
        # records keep microseconds: allow their rounding, nothing more
        assert start <= r.t_first + 2e-6
        assert end >= r.t_first - 2e-6
        assert e["attrs"]["slot"] in (0, 1)
        assert e["attrs"]["prompt_len"] == len(r.prompt)
        assert e["attrs"]["queue_wait_ms"] >= 0
    # one clock a decode round: the step histogram is fed by the spans
    decodes = FLIGHT.events(type="span", prefix="serve.decode")
    samples = FLIGHT.events(type="span", prefix="serve.sample")
    assert len(decodes) == len(samples) == eng.decode_steps > 0
    h = obs.REGISTRY.get("serve.decode.step_ms")
    assert h.count == len(decodes)
    assert h.sum == pytest.approx(sum(e["dur_ms"] for e in decodes))


def test_ligo_hop_spans_nest_under_grow(small_params):
    data = iter([dummy_batch(TINY, 2, 16, "train")] * 4)
    grow(small_params, TINY, BIG, method="ligo",
         key=jax.random.PRNGKey(1), data_it=data, ligo_steps=4,
         ligo_scan_chunk=2)
    spans = FLIGHT.events(type="span")
    by_id = {e["span_id"]: e for e in spans}

    def path(e):
        p = by_id.get(e["parent_id"])
        return (path(p) if p else []) + [e["name"]]

    paths = {"/".join(path(e)) for e in spans}
    assert {"grow", "grow/ligo.init", "grow/ligo.phase",
            "grow/ligo.phase/ligo.chunk",
            "grow/ligo.phase/ligo.chunk/ligo.batches",
            "grow/ligo.phase/ligo.chunk/ligo.launch",
            "grow/ligo.phase/ligo.chunk/ligo.sync",
            "grow/grow.params"} <= paths
    (root,) = [e for e in spans if e["name"] == "grow"]
    assert root["attrs"] == {"method": "ligo", "src": TINY.name,
                             "dst": BIG.name, "mesh": "none", "devices": 1}
    launches = [e for e in spans if e["name"] == "ligo.launch"]
    assert len(launches) == 2
    # the chunk program is traced on the first launch of the phase only
    assert [e["attrs"]["traced"] for e in launches] == [1, 0]
