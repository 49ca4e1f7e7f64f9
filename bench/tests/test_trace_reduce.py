"""The reduction from a profiler trace to busy time, idle share, top device
operations and labelled idle gaps: on hand-made events, and on a small
trace recorded on one TPU v5e."""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_summary_of_known_events():
    # a loop op holding two ops of its body, then a lone op, then one op
    # after the window
    devices = {"/device:TPU:0": [("%while", 0.0, 150.0),
                                 ("%fusion.1", 10.0, 60.0),
                                 ("%fusion.2", 80.0, 50.0),
                                 ("%dot", 300.0, 100.0),
                                 ("%late", 600.0, 50.0)]}
    host = [("bench/window", -10.0, 490.0),
            ("bench/step_dispatch", 150.0, 290.0)]
    s = trace_reduce.summarize(devices, host)
    # busy: [0, 150) and [300, 400) inside the window [-10, 490)
    assert s["busy_s"] == pytest.approx(250e-9)
    assert s["window_s"] == pytest.approx(500e-9)
    assert s["idle_share"] == pytest.approx(0.5)
    # each op's own time: the loop keeps 150 - 60 - 50
    assert s["device_ops"] == [["%dot", pytest.approx(100e-9)],
                               ["%fusion.1", pytest.approx(60e-9)],
                               ["%fusion.2", pytest.approx(50e-9)],
                               ["%while", pytest.approx(40e-9)]]
    gaps = s["idle_gaps"]
    assert gaps[0] == ["bench/step_dispatch", pytest.approx(150e-9)]
    assert gaps[1] == ["after bench/step_dispatch", pytest.approx(90e-9)]
    assert gaps[2] == ["no host span", pytest.approx(10e-9)]


def test_union_and_average_over_devices():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    devices = {"/device:TPU:0": [("a", 0.0, 10.0)],
               "/device:TPU:1": [("a", 0.0, 30.0)]}
    s = trace_reduce.summarize(devices, [], window=(0.0, 40.0))
    assert s["busy_s"] == pytest.approx(20e-9)
    assert s["n_devices"] == 2


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.summarize({}, [("bench/window", 0.0, 1.0)])


def test_recorded_tpu_trace():
    """Five steps of a jitted bf16 matmul chain with host gaps, recorded on
    one TPU v5e with the harness's profiler options (``bench/harness.py``)."""
    s = trace_reduce.reduce(os.path.join(DATA, "tpu_v5e_steps.xplane.pb"))
    assert s["n_devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert 0 < s["idle_share"] < 1
    assert s["device_ops"] and all(t > 0 for _, t in s["device_ops"])
    assert any(label.startswith(("bench/", "after bench/"))
               for label, _ in s["idle_gaps"])
