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


@pytest.mark.parametrize("name, collective", [
    ("%all-gather.10", True),
    ("%all-gather-start.1", True),
    ("%all-gather-done.1", True),
    ("%all-reduce", True),
    ("%all-reduce-start.3", True),
    ("%reduce-scatter-fusion.2", True),
    ("%collective-permute-done", True),
    ("%all-to-all.4", True),
    ("%fusion.47", False),
    ("%multiply_multiply_fusion.1", False),
    ("%select_and_scatter.21", False),
])
def test_collective_names(name, collective):
    assert tr.is_collective(name) == collective


def exposed(device):
    s = tr.summarize(host_steps([(0, 100)]), device)
    return {d: (v["collective_ns"], v["exposed_collective_ns"]) for d, v in s["devices"].items()}


def test_collective_hidden_under_a_conv_is_not_exposed():
    assert exposed({0: [("%fusion.1", 10, 60, "kOutput"), ("%all-gather.1", 20, 50, "")]}) \
        == {0: (30, 0)}


def test_collective_half_exposed():
    assert exposed({0: [("%fusion.1", 10, 40, "kOutput"), ("%all-reduce.1", 30, 50, "")]}) \
        == {0: (20, 10)}


def test_async_start_and_done_halves():
    """The start half is exposed only where it runs past the conv's
    start; the done half, where the device waits, is exposed; the done
    halves that overlap count once."""
    device = {0: [("%all-gather-start.1", 0, 10, ""), ("%fusion.2", 5, 50, "kOutput"),
                  ("%all-gather-done.1", 50, 70, ""), ("%all-reduce-done", 65, 75, "")]}
    assert exposed(device) == {0: (10 + 20 + 10, 5 + 25)}


def test_exposed_collective_ms_takes_the_worst_device():
    from chip_bench import run

    device = {0: [("%fusion.1", 0, 40, "kOutput"), ("%all-gather.1", 30, 50, "")],
              1: [("%fusion.1", 0, 20, "kOutput"), ("%all-gather-done.1", 20, 60, "")],
              2: [("%add", 0, 10, "")]}
    summary = tr.summarize(host_steps([(0, 100), (100, 200)]), device)
    assert summary["collectives"] == ["%all-gather-done.1", "%all-gather.1"]
    m = type("M", (), {"trace": summary})()
    reader = run.load_module("metrics", "exposed_collective_ms")
    assert reader.read(m) == 40 / 1e6 / 2  # device 1's 40 ns over two steps
    no_collective = tr.summarize(host_steps([(0, 100)]), {0: [("%add", 0, 10, "")]})
    assert reader.read(type("M", (), {"trace": no_collective})()) is None
    assert reader.read(type("M", (), {"trace": None})()) is None


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
