"""The trace reduction, on a trace recorded on a TPU v5e
(``chipbench/tools/record_trace.py``: 3 x (restore, 10 jitted decode
steps, idle)) and on hand-made events."""
from __future__ import annotations

import pytest

from tiny import BENCH

from lib import trace as T
from lib.common import BenchError

SAMPLE = BENCH / "data" / "trace_sample.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    return T.read(str(SAMPLE))


def test_sample_holds_spans_and_one_device(raw):
    names = [n for n, _, _ in raw["spans"]]
    assert names.count("window") == 1
    assert names.count("decode") == names.count("restore") == 3
    assert list(raw["devices"]) == ["/device:TPU:0"]


def test_sample_reduces_to_busy_steps_and_gaps(raw):
    s = T.reduce(raw)
    assert 0 < s["busy_s"] < s["window_s"]
    secs, n = T.module_time(s, "decode_step")
    assert n == 30
    assert 0 < secs < s["window_s"]
    idle = dict(s["idle_gaps"])
    assert set(idle) <= {"restore", "decode", "idle", "outside spans"}
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"],
                                               rel=1e-6)
    # the host sleeps in restore and idle; the device waits there
    assert idle["restore"] > idle["decode"] and idle["idle"] > idle["decode"]
    assert all(" " not in name for name, _ in s["device_ops"])


def test_gaps_go_to_the_innermost_span():
    raw = {"spans": [("window", 0, 100), ("turn", 10, 90),
                     ("restore", 20, 40)],
           "devices": {"/device:TPU:0": {
               "ops": [("%fusion.1 = f(x)", 0, 10), ("%while.2 = w", 40, 60),
                       ("%fusion.3 = g(y)", 45, 55), ("%fusion.1 = f(x)",
                                                      90, 100)],
               "modules": [("jit_step(1)", 40, 60)]}}}
    s = T.reduce(raw)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(40e-9)
    idle = dict(s["idle_gaps"])
    assert idle["restore"] == pytest.approx(20e-9)
    assert idle["turn"] == pytest.approx(40e-9)
    assert dict(s["device_ops"]) == {"fusion.1": pytest.approx(20e-9),
                                     "fusion.3": pytest.approx(10e-9)}
    assert T.module_time(s, "step") == (pytest.approx(20e-9), 1)


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(BenchError, match="no device operations"):
        T.reduce({"spans": [("window", 0, 10)],
                  "devices": {"/device:TPU:0": {"ops": [], "modules": []}}})
    with pytest.raises(BenchError, match="no cb:window"):
        T.reduce({"spans": [], "devices": {}})
