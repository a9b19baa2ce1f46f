"""The trace reduction: busy-interval union, idle gaps and their labels,
kernel and XLA op classification."""
import gzip

import numpy as np
import pytest

from conftest import ROOT

from bench import trace as tr


def test_union_merges_overlaps_and_drops_empty_intervals():
    start = np.array([5.0, 0.0, 2.0, 10.0, 12.0, 20.0])
    end = np.array([6.0, 3.0, 4.0, 12.0, 15.0, 20.0])
    assert tr.union(start, end).tolist() == [[0.0, 4.0], [5.0, 6.0],
                                             [10.0, 15.0]]
    assert tr.union(np.array([]), np.array([])).shape == (0, 2)


def test_labels_take_the_span_covering_most_of_each_gap():
    spans = [("wait", 10.0, 30.0), ("invoke", 0.0, 10.0), ("fetch", 31.0, 35.0)]
    gaps = np.array([[8.0, 14.0], [2.0, 9.0], [40.0, 50.0], [29.0, 36.0],
                     [30.0, 31.0]])
    assert tr.labels(spans, gaps) == ["wait", "invoke", "other", "fetch",
                                      "other"]


def _synthetic():
    names = ["fusion.1", "systolic_matmul", "fusion.2", "systolic_matmul"]
    kernels = [None, "systolic_matmul", None, "systolic_matmul"]
    start = np.array([10.0, 20.0, 25.0, 60.0])
    end = np.array([20.0, 30.0, 40.0, 70.0])
    spans = [("invoke", 0.0, 12.0), ("wait", 12.0, 72.0),
             ("fetch", 72.0, 90.0), ("pick", 90.0, 100.0)]
    return tr.Trace(names, kernels, np.zeros(4, dtype=int), start, end,
                    spans, (0.0, 100.0), 1)


def test_busy_kernel_and_xla_time_of_a_synthetic_trace():
    t = _synthetic()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx(40e-9)          # [10,40] + [60,70]
    assert t.kernel_s("systolic") == pytest.approx(20e-9)
    assert t.kernel_s("affine") == 0.0
    assert t.xla_s() == pytest.approx(25e-9)
    gaps = [(lab, b - a) for lab, a, b in t.idle_gaps()]
    assert gaps == [("invoke", 10.0), ("wait", 20.0), ("fetch", 30.0)]
    bd = t.breakdown()
    assert bd["device_ops"][0] == ["systolic_matmul", pytest.approx(20e-9)]
    assert bd["idle_gaps"][0] == ["fetch", pytest.approx(30e-9)]


RECORDED = ROOT / "tests" / "bench" / "data" / "yolov3_tiny.xplane.pb.gz"


def test_recorded_chip_trace(tmp_path):
    """A traced window of the tiny YOLOv3 (width 0.125, 64x64) on a TPU
    v5e: every request runs its 21 convolutions through the systolic
    kernel and its f1 through the vector-engine kernel; the device's ops
    land inside the requests' host spans once the clock is shifted."""
    path = tmp_path / "yolov3_tiny.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    t = tr.load(path)
    invokes = [s for s in t.spans if s[0] == "invoke"]
    n = len(invokes)
    assert n > 0
    assert sum(k == "systolic_matmul" for k in t.kernels) == 21 * n
    assert sum(k == "fused_affine_act" for k in t.kernels) == n
    assert set(t.kernels) == {None, "systolic_matmul", "fused_affine_act"}
    assert t.shift_ns > 0
    first = min(s for _, s, _ in invokes)
    assert t.start.min() >= first          # no op before its request
    busy = t.busy_s()
    assert 0 < busy < t.window_s
    assert t.kernel_s("") + t.xla_s() >= busy      # every op, summed
    gaps = t.idle_gaps()
    assert {g[0] for g in gaps} <= set(tr.SPANS) | {"other"}
    assert sum(b - a for _, a, b in gaps) / 1e9 == pytest.approx(
        t.window_s - busy, rel=1e-9)
