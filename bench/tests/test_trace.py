"""The trace reduction against a small trace recorded on one TPU v5e
(tests/data/sample.xplane.pb, made by tests/data/make_sample_trace.py:
three 180.85 us programs, each followed by a 20 ms host sleep)."""

from pathlib import Path

import pytest

from harness import trace

SAMPLE = str(Path(__file__).resolve().parent / "data" / "sample.xplane.pb")


def test_sample_trace():
    s = trace.reduce(SAMPLE)
    assert s["n_devices"] == 1 and s["n_programs"] == 3
    # the three programs' device durations, all inside the window once the
    # device clock is moved onto the host's
    assert s["busy_s"] == pytest.approx((180851 + 180854 + 180875) * 1e-9, abs=1e-12)
    assert s["window_s"] == pytest.approx(0.066379754)
    # the device runs 1.385 ms "before" the host enqueues it on its own clock
    assert s["clock_shift_ns"] == [1385176]
    labels = [name for name, _ in s["idle_gaps"][:3]]
    assert labels == ["sleep"] * 3
    assert all(0.020 < sec < 0.023 for _, sec in s["idle_gaps"][:3])
    names = [n for n, _ in s["device_ops"]]
    assert names[:2] == ["fusion", "convolution_tanh_fusion"]
    assert sum(sec for _, sec in s["device_ops"]) <= s["busy_s"]


def test_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.gaps([(0, 3), (5, 9)], 1, 12) == [(3, 5), (9, 12)]
    assert trace.covered([(0, 3), (5, 9)], 2, 6) == 2


def test_self_time_of_nested_ops():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 90), ("c", 50, 60), ("d", 120, 130)]
    assert trace.self_times(ops) == {"while": 30, "a": 20, "b": 40, "c": 10, "d": 10}
    assert trace.op_name("%fusion.12 = f32[2]{0} fusion(f32[2]{0} %p)") == "fusion.12"
