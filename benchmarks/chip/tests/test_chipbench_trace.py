"""The trace reduction, on a hand-built trace with numbers worked out by
hand and on a small trace recorded on the CPU."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from benchmarks.chip.lib import trace as T  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "cpu_window.xplane.pb")

# Times in microseconds (the proto's offsets are picoseconds). The window
# spans 0..100; device ops: A 10..30, B 20..40 (overlaps A), _kernel
# 60..70, A 90..110 (runs past the window's end). Host spans: step 0..45,
# wait 45..95. Busy = [10, 40] + [60, 70] + [90, 100] = 50 of 100.
PROTO = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 20000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.A" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.B" } }
  event_metadata { key: 3 value { id: 3 name: "_kernel" } }
  event_metadata { key: 4 value { id: 4 name: "jit_prefill_one(7)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 45000000 }
    events { metadata_id: 3 offset_ps: 45000000 duration_ps: 50000000 }
    events { metadata_id: 4 offset_ps: 50000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(f)" } }
}
"""


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return T.load(ProfileData.from_text_proto(PROTO))


def test_busy_union_and_idle_share(hand):
    s = T.summarize(hand)
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(50e-6)
    assert s.idle_share == pytest.approx(0.5)
    assert s.n_devices == 1


def test_time_by_name_clipped_to_window(hand):
    s = T.summarize(hand)
    assert s.ops_s["fusion.A"] == pytest.approx(30e-6)     # 20 + 10 inside
    assert s.ops_s["fusion.B"] == pytest.approx(20e-6)
    assert s.time_of(r"^_kernel") == pytest.approx(10e-6)
    assert s.count_of(r"^fusion\.A$") == 2
    assert s.time_of("prefill_one", modules=True) == pytest.approx(30e-6)


def test_gaps_named_by_host_span(hand):
    # gaps: 0..10 (step), 40..60 (step 40..45, wait 45..60: wait wins),
    # 70..90 (wait)
    s = T.summarize(hand)
    assert s.idle_by_span["bench.step"] == pytest.approx(10e-6)
    assert s.idle_by_span["bench.wait"] == pytest.approx(40e-6)
    assert "PjitFunction(f)" not in s.idle_by_span
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.A", pytest.approx(30e-6)]
    assert [k for k, _ in b["idle_gaps"]] == ["bench.wait", "bench.step"]


def test_idle_share_between(hand):
    # 50..100 us: busy 60..70 and 90..100 = 20 of 50
    assert T.idle_share_between(hand, 50e-6, 100e-6) == pytest.approx(0.6)


@pytest.mark.parametrize("name,fwd,bwd", [
    ("ligo_blend_expand_grouped.2", True, False),
    ("jvp_jit_ligo_blend_expand_grouped__.2", True, False),
    ("transpose_jvp_jit_ligo_blend_expand_bwd_fused___.1", False, True),
    ("_kernel", True, False),
    ("_bwd_kernel", False, True),
    ("jvp_jit_ligo_blend_expand_grouped_ref_.4", False, False),
    ("fusion.12", False, False),
])
def test_kernel_names(name, fwd, bwd):
    import re

    from benchmarks.chip.lib import kernels as K
    assert bool(re.search(K.FWD, name)) == fwd
    assert bool(re.search(K.BWD, name)) == bwd


def test_union_and_gaps_primitives():
    assert T.union([(5, 8), (0, 2), (1, 3), (7, 12)], 0, 10) == [
        (0, 3), (5, 10)]
    evs = [T.Event("x", 2, 4), T.Event("y", 6, 7)]
    assert T.gaps(evs, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert T.busy_ns(evs, 3, 10) == 2


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.window_of(T.Trace(devices={"d": [T.Event("x", 0, 1)]}))


def test_recorded_cpu_trace():
    tr = T.load(FIXTURE, **T.CPU_LINES)
    s = T.summarize(tr)
    lo, hi = T.window_of(tr)
    assert hi > lo
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    assert s.count_of(r"^dot_general") == 3          # three calls of f
    names = [k for k, _ in s.breakdown()["idle_gaps"]]
    assert names[0] == "bench.host_work"             # the host's sleeps
    assert len(s.breakdown()["device_ops"]) <= 10
