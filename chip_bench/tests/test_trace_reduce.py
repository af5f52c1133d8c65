"""The reduction from trace events to the per-layer numbers."""
import glob
import gzip
import os

import pytest

from chip_bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_length():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.length([(0, 10), (5, 15)]) == 15


def test_classify_tpu_op_names():
    name = ("%fusion.43 = f32[128,16,16,500]{0,3,2,1} fusion(f32[128,16,16,1500] %g, "
            "f32[5,5,500,1500] %w), kind=kOutput, calls=%fused_computation.80")
    assert tr.classify(name) == ("%fusion.43", "kOutput")
    assert tr.is_conv(*tr.classify(name))
    assert tr.is_conv("%conv2d_dw_pallas.3", "")
    assert not tr.is_conv("%power_multiply_fusion", "kLoop")


def host_steps(spans):
    return tr.nest([("bench.train", s, e) for s, e in spans]
                   + [("bench.sync", s + 50, e) for s, e in spans])


def test_summarize_synthetic():
    host = host_steps([(0, 100), (100, 200)])
    device = {
        0: [("%fusion.1", 10, 40, "kOutput"), ("%conv2d_pallas", 40, 60, ""),
            ("%all-gather-done.1", 60, 80, ""), ("%add", 70, 75, ""),
            ("%fusion.2", 110, 190, "kLoop"), ("%copy", 250, 300, "")],
        1: [("%all-gather-done.1", 0, 50, ""), ("%fusion.1", 20, 30, "kOutput")],
    }
    s = tr.summarize(host, device)
    assert s["window_ns"] == 200 and s["steps"] == 2
    d0, d1 = s["devices"][0], s["devices"][1]
    assert d0["busy_ns"] == 30 + 20 + 20 + 80  # the copy lies outside the window
    assert d0["conv_ns"] == 50
    assert d1["busy_ns"] == 50 and d1["conv_ns"] == 10
    # device 0 is idle 0-10, 80-110, 190-200; the longest gap first,
    # named by the innermost span open at its middle
    assert s["idle_gaps"] == [["bench.sync", 30e-9], ["bench.train", 10e-9],
                              ["bench.sync", 10e-9]]
    assert s["device_ops"][0] == ["%fusion.2", 80e-9]


def test_summarize_without_steps_is_none():
    assert tr.summarize([], {0: [("%add", 0, 1, "")]}) is None


@pytest.mark.parametrize("name", sorted(
    os.path.basename(p) for p in glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))))
def test_recorded_chip_trace(name):
    """Traces recorded on the chip by the harness's traced run, with
    the expected numbers beside them."""
    import json

    from jax.profiler import ProfileData

    with gzip.open(os.path.join(DATA, name), "rb") as f:
        prof = ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, name.replace(".xplane.pb.gz", ".json"))) as f:
        want = json.load(f)
    s = tr.summarize(*tr.load_events(prof))
    assert s["steps"] == want["steps"]
    assert sorted(s["devices"]) == want["devices"]
    for d in s["devices"].values():
        assert 0 < d["busy_ns"] <= s["window_ns"]
        assert 0 < d["conv_ns"] <= d["busy_ns"]
    assert len(s["device_ops"]) == 10 and len(s["idle_gaps"]) <= 10
