"""The program's spans and host<->device copy counters (``repro.tracing``)
on the CPU: a tiny cluster train step (xla master, a numpy or pallas
member, inproc, two microbatches) under the profiler, its trace reduced
by ``chip_bench/spans.py``."""
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import CNNConfig
from repro.core.cluster import HeteroCluster
from repro.models.cnn import init_cnn, make_cluster_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chip_bench import spans  # noqa: E402

CFG = CNNConfig(arch_id="cifar_cnn_tiny", c1_kernels=4, c2_kernels=6, image_size=8)
BATCH, MICRO = 4, 2
FIELD_SPANS = {
    "comm_s": ("cluster.scatter",),
    "conv_s": ("cluster.gather",),
    "master_conv_s": ("cluster.master_conv",),
    "gather_wait_s": ("cluster.gather_wait",),
    "comp_s": ("cluster.stage_fwd", "cluster.stage_bwd", "cluster.head"),
}
STEP_SPANS = ("cluster.scatter", "cluster.gather", "cluster.master_conv",
              "cluster.gather_wait", "cluster.assemble", "cluster.stage_fwd",
              "cluster.stage_bwd", "cluster.head", "cnn.update",
              "host.to_device", "host.to_host", "device.wait")


def _cluster(partition="kernel", member="numpy"):
    c = HeteroCluster([1.0, 1.0], ["xla", member], pipeline=True, microbatches=MICRO,
                      partition=partition, comp_aware=False, transport="inproc")
    c.probe(image_size=CFG.image_size, in_channels=CFG.image_channels,
            kernel_size=CFG.kernel_size, num_kernels=CFG.c1_kernels, batch=BATCH)
    c.probe_times = [1.0, 1.0]  # an even split: conv1 2 + 2 kernels, conv2 3 + 3
    return c


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    params = init_cnn(jax.random.key(seed), CFG)
    images = rng.normal(size=(BATCH, CFG.image_size, CFG.image_size, 3)).astype(np.float32)
    labels = rng.integers(0, CFG.num_classes, size=BATCH).astype(np.int32)
    return params, images, labels


def _trace_file(tmp_path):
    (path,) = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path) for f in fs
               if f.endswith(".xplane.pb")]
    return path


def _traced_step(tmp_path, cluster):
    """Warm step untraced, then the probe again and one step under the
    profiler, the step in a ``bench.train`` span: ``(reduction, spans
    of the trace, counters' change, traced totals' change)``."""
    step = make_cluster_train_step(cluster, CFG, lr=0.01)
    params, images, labels = _inputs()
    params, _, _ = step(params, images, labels)
    with jax.profiler.trace(str(tmp_path)):
        cluster.probe(**cluster._probe_kwargs)
        cluster.probe_times = [1.0, 1.0]
        cluster.reset_stats()
        bytes0, totals0 = tracing.counters(), tracing.span_totals(traced=True)
        with jax.profiler.TraceAnnotation("bench.train"):
            step(params, images, labels)
        bytes1, totals1 = tracing.counters(), tracing.span_totals(traced=True)
    found, device = spans.load_file(_trace_file(tmp_path))
    moved = {k: bytes1[k] - bytes0[k] for k in bytes1}
    traced = {k: tuple(a - b for a, b in zip(v, totals0.get(k, (0.0, 0.0, 0))))
              for k, v in totals1.items()}
    return spans.reduce(found, device), found, moved, traced


def _span_s(red, names):
    return sum(row["total_ns"] for n in names for row in red["spans"].get(n, {}).values()) / 1e9


def test_step_spans_sit_on_their_threads_and_feed_layer_timing(tmp_path):
    c = _cluster()
    try:
        red, found, _moved, traced = _traced_step(tmp_path, c)
    finally:
        c.shutdown()
    assert red is not None and red["steps"] == 1
    step_thread = red["step_thread"]
    for name in STEP_SPANS:
        assert name in red["spans"], name
    for name in ("member.conv", "member.bwd"):
        threads = set(red["spans"][name])
        assert threads and step_thread not in threads, name
    (window,) = [(s, e) for n, s, e, t in found if n == "bench.train"]
    for n, s, e, t in found:
        if n.startswith("cluster.") and n != "cluster.probe":
            assert t == step_thread and window[0] <= s <= e <= window[1], n
    # the probe ran in the trace, before the window: on the step thread,
    # clipped out of the window's reduction
    assert [t for n, _, _, t in found if n == "cluster.probe"] == [step_thread]
    assert "cluster.probe" not in red["spans"]
    assert c.probe_s > 0.0
    # every LayerTiming field is the summed duration of its span(s)
    for field, names in FIELD_SPANS.items():
        assert abs(getattr(c.timing, field) - _span_s(red, names)) < 1e-3, field
    assert c.timing.recompute_s == 0.0 and "cluster.recover" not in red["spans"]
    # the program's own totals while traced hold the same spans
    kept = {}
    for (name, _thread), (total_s, _self, count) in traced.items():
        if count:
            kept[name] = kept.get(name, 0.0) + total_s
    assert abs(kept["cluster.gather_wait"] - c.timing.gather_wait_s) < 1e-9
    for name in STEP_SPANS:
        assert abs(kept[name] - _span_s(red, (name,))) < 1e-3, name


def reckoned_bytes(cfg, batch, micro, s0, s1, host_member=True):
    """Bytes a step of ``make_cluster_train_step`` copies between host
    and device with an xla master holding ``s0``/``s1`` kernels of
    conv1/conv2 and one member the rest: ``(h2d, d2h)``.  Images and
    labels go up once, the microbatches' losses and correct counts come
    down once; everything else stays on the device, except what a host
    member (``numpy``) reads and returns: its inputs come down, its
    outputs go up to be assembled there."""
    mb, k, cin, c1, c2 = batch // micro, cfg.kernel_size, cfg.image_channels, \
        cfg.c1_kernels, cfg.c2_kernels
    h1 = cfg.image_size
    h2 = h1 // 2
    up = batch * h1 * h1 * cin * 4 + batch * 4   # images, int32 labels
    down = micro * 2 * 4                         # (loss, correct) per microbatch
    if not host_member:
        return up, down
    n0, n1 = c1 - s0, c2 - s1                    # the member's kernels
    x0, x1 = mb * h1 * h1 * cin, mb * h2 * h2 * c1        # conv inputs
    y0, y1 = mb * h1 * h1 * n0, mb * h2 * h2 * n1         # its outputs
    w0, w1 = k * k * cin * n0, k * k * c1 * n1            # its shards
    down_mb = x0 + x1                        # fwd inputs
    down_mb += (x1 + y1) + (x0 + y0)         # bwd inputs: x, grad slice
    up_mb = y0 + y1                          # fwd outputs
    up_mb += (x1 + w1) + (x0 + w0)           # bwd outputs: full dx, dw shard
    # its shards come down once a step: the weight cache holds the host copy
    return up + 4 * micro * up_mb, down + 4 * (micro * down_mb + w0 + w1)


@pytest.mark.parametrize("member", ["numpy", "pallas:interpret"])
def test_host_copy_bytes_are_the_hand_reckoned_ones(tmp_path, member):
    c = _cluster(member=member)
    try:
        assert [list(c.shares_for(n)) for n in (4, 6)] == [[2, 2], [3, 3]]
        before = tracing.counters()
        _red, _found, moved, _traced = _traced_step(tmp_path, c)
    finally:
        c.shutdown()
    host = member == "numpy"
    assert (moved["h2d_bytes"], moved["d2h_bytes"]) == reckoned_bytes(
        CFG, BATCH, MICRO, 2, 3, host_member=host)
    # every gather of the traced step (2 layers x 2 microbatches, forward
    # and backward) assembled on the device; none of either step on the host
    assert moved["device_assembles"] == 8
    assert tracing.counters()["host_assembles"] == before["host_assembles"]


@pytest.mark.parametrize("member", ["numpy", "pallas:interpret"])
def test_step_uploads_nothing_behind_the_counters(member):
    """A warm step with every host-to-device transfer that does not go
    through ``repro.tracing`` refused (on every thread): a jit call given
    numpy, an eager op given a Python number or an index would raise.
    Off a chip the device's arrays are host memory, so the other
    direction is checked on the chip (PERF.md)."""
    c = _cluster(member=member)
    try:
        step = make_cluster_train_step(c, CFG, lr=0.01)
        params, images, labels = _inputs()
        params, loss0, _ = step(params, images, labels)
        jax.config.update("jax_transfer_guard_host_to_device", "disallow")
        try:
            _params, loss1, _ = step(params, images, labels)
        finally:
            jax.config.update("jax_transfer_guard_host_to_device", "allow")
    finally:
        c.shutdown()
    assert np.isfinite(loss0) and np.isfinite(loss1)


@pytest.mark.parametrize("partition, ops", [
    ("kernel", ("member.conv", "member.bwd")),
    ("spatial", ("member.sconv", "member.sbwd")),
])
def test_member_spans_per_op(partition, ops):
    c = _cluster(partition)
    try:
        step = make_cluster_train_step(c, CFG, lr=0.01)
        before = tracing.span_totals()
        step(*_inputs())
        after = tracing.span_totals()
    finally:
        c.shutdown()
    ran = {name for (name, thread), v in after.items()
           if name.startswith("member.") and v[2] > before.get((name, thread), (0, 0, 0))[2]}
    assert ran == set(ops)


def test_recovered_shard_feeds_recompute_s():
    """A member evicted mid-step: the master recomputes its in-flight
    shards under ``cluster.recover``, which feeds ``recompute_s``."""
    c = _cluster()
    try:
        step = make_cluster_train_step(c, CFG, lr=0.01)
        params, images, labels = _inputs()
        step(params, images, labels)
        c.reset_stats()
        gather = c.gather_conv
        fired = []

        def gather_then_evict(p):
            if not fired:
                fired.append(True)
                c.evict(c.slave_ids[0])
            return gather(p)

        c.gather_conv = gather_then_evict
        before = tracing.span_totals()
        step(params, images, labels)
        after = tracing.span_totals()
    finally:
        c.shutdown()
    key = ("cluster.recover", threading.current_thread().name)
    seconds = after[key][0] - before.get(key, (0.0, 0.0, 0))[0]
    assert c.timing.recompute_s > 0.0
    assert abs(seconds - c.timing.recompute_s) < 1e-9


def test_copies_count_only_real_crossings():
    a = np.ones((3, 5), np.float32)
    b0 = tracing.counters()
    d = tracing.to_device(a)
    h = tracing.to_host(d, np.float64)
    tracing.to_host(a)       # numpy in: no crossing
    tracing.to_device(d)     # already on the device: no crossing
    b1 = tracing.counters()
    assert h.dtype == np.float64 and np.array_equal(h, a)
    assert b1["h2d_bytes"] - b0["h2d_bytes"] == a.nbytes
    assert b1["d2h_bytes"] - b0["d2h_bytes"] == a.nbytes


def test_self_time_leaves_out_nested_spans():
    before = tracing.span_totals()
    with tracing.span("cluster.test_outer"):
        with tracing.span("cluster.test_inner") as inner:
            sum(range(10000))
    after = tracing.span_totals()
    thread = threading.current_thread().name
    outer = after[("cluster.test_outer", thread)]
    prev = before.get(("cluster.test_outer", thread), (0.0, 0.0, 0))
    total, self_s = outer[0] - prev[0], outer[1] - prev[1]
    assert abs((total - self_s) - (inner.end - inner.start)) < 1e-12
