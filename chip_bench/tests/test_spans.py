"""The reduction of the program's spans (``chip_bench/spans.py``) and the
metrics that read the program's own totals: on synthetic spans, on a
trace recorded on the chip, and through a traced rehearsal of a cluster
cell on the CPU."""
import math
import os
import sys

import pytest

from chip_bench import spans, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "cnn50_cluster_hetero.xplane.pb.gz")
READERS = ("host_copy_ms", "host_copy_mb", "master_stage_ms", "slowest_member_ms", "probe_s")

# one step on thread "a" (the step thread), a member on thread "b"
SYNTHETIC = [
    ("bench.train", 0, 100, "a"),
    ("bench.step", 5, 95, "a"),
    ("cluster.gather", 10, 60, "a"),
    ("cluster.master_conv", 20, 40, "a"),
    ("host.to_host", 25, 30, "a"),
    ("cluster.stage_fwd", 70, 90, "a"),
    ("member.conv", 5, 50, "b"),
    ("host.to_host", 40, 45, "b"),
    ("member.conv", 90, 130, "b"),   # runs past the window's end
    ("cluster.probe", -50, -10, "a"),  # set-up, before the window
    ("PjitFunction(f)", 21, 22, "a"),  # not the program's: never read
]


def row(red, name, thread):
    return red["spans"][name][thread]


def test_self_time_nests_program_spans_per_thread():
    red = spans.reduce(SYNTHETIC)
    assert (red["window_ns"], red["steps"], red["step_thread"]) == (100, 1, "a")
    assert row(red, "cluster.gather", "a") == {"total_ns": 50, "self_ns": 30, "count": 1}
    assert row(red, "cluster.master_conv", "a") == {"total_ns": 20, "self_ns": 15, "count": 1}
    assert row(red, "host.to_host", "a")["self_ns"] == 5
    # threads apart: the member's copy is its own row, under its own conv
    assert row(red, "host.to_host", "b") == {"total_ns": 5, "self_ns": 5, "count": 1}
    assert row(red, "member.conv", "b") == {"total_ns": 45 + 10, "self_ns": 40 + 10, "count": 2}
    assert set(red["spans"]) == {"cluster.gather", "cluster.master_conv", "host.to_host",
                                 "cluster.stage_fwd", "member.conv"}
    assert red["idle_ns"] == {}


def test_clipping_to_the_window():
    red = spans.reduce(SYNTHETIC)
    assert "cluster.probe" not in red["spans"]  # wholly before the window
    assert row(red, "member.conv", "b")["total_ns"] == 55  # 90..130 cut at 100
    assert spans.reduce([sp for sp in SYNTHETIC if sp[0] != "bench.train"]) is None


def test_idle_split_by_innermost_span_on_the_step_thread():
    device = {0: [("%a", 0, 10, ""), ("%b", 60, 70, ""), ("%c", 85, 100, "")],
              1: [("%d", 0, 100, "")]}
    red = spans.reduce(SYNTHETIC, device)
    # idle 10..60 and 70..85; the member's spans on "b" never label it
    assert red["idle_ns"] == {"cluster.gather": 30, "cluster.master_conv": 15,
                              "host.to_host": 5, "cluster.stage_fwd": 15}
    # gaps under no program span are named by the benchmark's span, or
    # "outside" where none is open
    split = spans.idle_split([("bench.train", 0, 100, "a"), ("bench.sync", 80, 100, "a")],
                             [(10, 20)], -10, 110)
    assert split == {"outside": 20, "bench.train": 70, "bench.sync": 20}


def test_program_totals_are_none_without_the_program(monkeypatch):
    """Where the program keeps no totals (``repro.tracing`` absent), the
    readers find nothing and do not raise."""
    import repro

    from chip_bench import run

    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing", raising=False)
    assert spans.program_totals() is None
    for name in READERS:
        assert run.load_module("metrics", name).read(object()) is None


@pytest.fixture(scope="module")
def recorded():
    return spans.load_file(RECORDED)


def test_recorded_chip_trace(recorded):
    """A short traced ``cnn50_cluster_hetero`` run on a TPU v5e."""
    found, device = recorded
    red = spans.reduce(found, device)
    assert red["steps"] >= 1
    step = red["step_thread"]
    for name in ("cluster.scatter", "cluster.gather", "cluster.master_conv",
                 "cluster.gather_wait", "cluster.assemble", "cluster.stage_fwd",
                 "cluster.stage_bwd", "cluster.head", "cnn.update",
                 "host.to_device", "host.to_host", "device.wait"):
        assert step in red["spans"][name], name
    members = set(red["spans"]["member.conv"]) | set(red["spans"]["member.bwd"])
    assert len(members) == 2 and step not in members  # the pallas and numpy members
    for per_thread in red["spans"].values():
        for r in per_thread.values():
            assert 0 <= r["self_ns"] <= r["total_ns"] <= red["window_ns"] and r["count"] > 0
    # the split covers device 0's idle time exactly
    summary = trace_reduce.summarize(
        trace_reduce.nest([(n, s, e) for n, s, e, t in found if t == step]), device)
    idle_ns = summary["window_ns"] - summary["devices"][0]["busy_ns"]
    assert sum(red["idle_ns"].values()) == idle_ns
    assert red["window_ns"] == summary["window_ns"]


def test_traced_rehearsal_reads_the_new_metrics():
    """``cnn50_cluster_hetero`` at tiny widths through the harness with
    the trace on: each new reader gives a finite value of 0 or more."""
    from test_run import drive, tiny

    cell = tiny("cnn50_cluster_hetero", trace=True)
    assert set(READERS) <= {m["name"] for m in cell.metrics}
    meas, checks, failed = drive(cell, cell.path_class(), trace=True)
    assert failed == 0 and meas.trace is not None
    for name in READERS:
        value = cell.reader(name).read(meas)
        assert value is not None and math.isfinite(value) and value >= 0.0, name
    assert cell.reader("host_copy_mb").read(meas) > 0.0
    assert cell.reader("probe_s").read(meas) > 0.0
