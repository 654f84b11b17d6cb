"""The trace reduction, on hand-made intervals and on a small trace
recorded on one v5e (``benchmark/tests/data/small.xplane.pb``)."""

import os

import numpy as np
import pytest

import tracereduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_merge_and_cover():
    m = tr.merge([[5, 7], [0, 2], [1, 3], [6, 9], [20, 21]])
    np.testing.assert_array_equal(m, [[0, 3], [5, 9], [20, 21]])
    np.testing.assert_allclose(tr.covered(m, [0, 2.5, 8, 10], [10, 6, 30,
                                                                  11]),
                               [7, 1.5, 2, 0])
    np.testing.assert_array_equal(tr.gaps(m, 1, 22),
                                  [[3, 5], [9, 20], [21, 22]])


def test_reduce_names_gaps_and_busy():
    ms = 1e6
    ops = {"/device:TPU:0": (np.array([10, 20, 70]) * ms,
                             np.array([30, 40, 90]) * ms,
                             ["fusion", "copy", "fusion"]),
           "/device:TPU:1": (np.array([10 * ms]), np.array([90 * ms]),
                             ["fusion"])}
    marks = [(0, 100 * ms, "bench/window"),
             (0, 45 * ms, "bench/request"),
             (45 * ms, 70 * ms, "bench/between"),
             (70 * ms, 100 * ms, "bench/request")]
    s = tr.reduce(ops, marks)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s_by_device"]["/device:TPU:0"] == pytest.approx(0.05)
    assert s["busy_s_by_device"]["/device:TPU:1"] == pytest.approx(0.08)
    assert s["busy_s"] == pytest.approx(0.065)
    assert s["device_ops"][0] == ["fusion", pytest.approx(0.06)]
    top = s["idle_gaps"][0]
    assert top[0] == "between requests" and top[1] == pytest.approx(0.03)
    # request 0 is 45 ms with 30 and 35 ms of device busy
    assert s["request_host_s"][0] == pytest.approx(0.045 - 0.0325)


def test_reduce_without_device_is_silent():
    assert tr.reduce({}, [(0, 1, "bench/window")]) is None
    assert tr.reduce({"/device:TPU:0": (np.zeros(1), np.ones(1), ["x"])},
                     []) is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace():
    ops, marks = tr.read_xplane(DATA)
    s = tr.reduce(ops, marks)
    assert s is not None and s["devices"] == ["/device:TPU:0"]
    assert 0 < s["busy_s"] < s["window_s"]
    assert len(s["request_host_s"]) == sum(
        1 for m in marks if m[2] == "bench/request")
    assert all(v >= 0 for v in s["request_host_s"])
    assert s["device_ops"] and s["idle_gaps"]
