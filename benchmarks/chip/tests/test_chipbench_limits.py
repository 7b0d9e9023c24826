"""``tools/limits.py``: each limit lies above the program's largest reading
and below the smallest reading that bounds it from above."""
import json

import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip.tools import limits as L

PROG = {"x": [0.01, 0.02]}
CONTROL = {"x": [0.5, 0.4]}
FAULTS = {"x": {"unchanged": [0.1, 0.2], "half_batch": [0.15]}}


@pytest.mark.parametrize("training,upper,by", [
    (False, 0.4, "control"),        # faults bound only a training cell
    (True, 0.1, "unchanged"),       # 3x the lower; half_batch is under 10x
])
def test_limit_between_lower_and_upper(training, upper, by):
    out, notes = L.limits(PROG, CONTROL, FAULTS, training)
    n = notes["x"]
    assert (n["lower"], n["upper"], n["upper_from"]) == (0.02, upper, by)
    assert 0.02 < out["x"] < upper
    # more of the room lies above the lower reading
    assert out["x"] / 0.02 > upper / out["x"]


def test_no_upper_reading_no_limit():
    out, notes = L.limits({"x": [0.1]}, {"x": [0.2]}, {}, training=True)
    assert out == {} and "limit" not in notes["x"]


def test_collect_reads_program_control_faults_and_errors(tmp_path):
    lines = [{"seed": 1, "fault": None, "program": {"x": 0.01},
              "control": {"x": 0.5}},
             {"seed": 2, "fault": None, "program": {"x": 0.02}},
             {"seed": 3, "fault": "unchanged", "program": {"x": 0.1}},
             {"seed": 4, "fault": None, "error": "RuntimeError()"}]
    p = tmp_path / "r.txt"
    p.write_text("noise\n" + "\n".join(json.dumps(x) for x in lines) + "\n")
    prog, ctl, faults, errors = L.collect([str(p)])
    assert prog == {"x": [0.01, 0.02]} and ctl == {"x": [0.5]}
    assert faults["x"] == {"unchanged": [0.1]} and len(errors) == 1
