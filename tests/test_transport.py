"""Transport conformance: the in-proc queue emulation, the real TCP
wire and the shared-memory rings must be interchangeable behind the
same contract.

One suite runs against ALL THREE transports: payload roundtrip fidelity
and FIFO order, canonical nbytes accounting (identical numbers on every
wire, with and without each codec stage), slave-error propagation, and
— subprocess wires — measured link bandwidth feeding the comm-aware
partitioner, subprocess slave numerics vs the single-device VJP on
every partition axis, device-array payloads crossing a real wire or a
narrowing codec exactly as numpy would, and orderly subprocess shutdown
on cluster close and after a master-side protocol exception.  Shm additionally proves
segment hygiene (nothing leaks into /dev/shm) and the inline fallback
for arrays larger than the ring.
"""
import functools
import threading

import numpy as np
import pytest

from repro.core.cluster.codec import WireCodec, resolve_wire_dtype
from repro.core.cluster.transport import (
    InProcTransport,
    ShmSlaveEndpoint,
    ShmTransport,
    TCPListener,
    TCPSlaveEndpoint,
    TCPTransport,
)
from repro.core.master_slave import HeteroCluster

TRANSPORTS = ("inproc", "tcp", "shm")


def _make_link(kind: str, wire_dtype=None, wire_codec=None, **chan_kw):
    """(master_channel, slave_endpoint, close) for any transport; the
    TCP/shm pairs cross a REAL localhost socket.  Each side gets its
    own codec instance, like the cluster builds per link."""
    dtype = resolve_wire_dtype(wire_dtype)

    def _codec():
        return WireCodec.from_spec(wire_codec, wire_dtype)

    if kind == "inproc":
        link = InProcTransport(None, dtype, wire_codec=_codec())
        return link, link.slave_endpoint(), link.close
    chan_cls, ep_cls = (
        (ShmTransport, ShmSlaveEndpoint) if kind == "shm"
        else (TCPTransport, TCPSlaveEndpoint)
    )
    listener = TCPListener()
    slave_box = {}

    def _connect():
        slave_box["ep"] = ep_cls(
            listener.host, listener.port, dtype, wire_codec=_codec()
        )

    t = threading.Thread(target=_connect)
    t.start()
    chan = chan_cls(
        listener.accept(timeout_s=10), dtype, wire_codec=_codec(), **chan_kw
    )
    t.join(timeout=10)
    slave = slave_box["ep"]

    def _close():
        chan.close()
        slave.close()
        listener.close()

    return chan, slave, _close


def _payload(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "x": rng.normal(size=(2, 4, 4, 3)).astype(np.float32),
        "nested": (np.arange(5, dtype=np.float64), [np.ones(3, np.float32)]),
        "ints": np.arange(4, dtype=np.int32),
        "flag": "keep-me",
    }


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_roundtrip_fifo_both_directions(kind):
    """Messages cross intact (nested containers, dtypes, strings) and in
    FIFO order, in both directions."""
    chan, slave, close = _make_link(kind)
    try:
        msgs = [_payload(s) for s in range(3)]
        for m in msgs:
            chan.write_to_slave(m)
        for m in msgs:
            got = slave.recv()
            assert got["flag"] == "keep-me"
            np.testing.assert_array_equal(got["x"], m["x"])
            np.testing.assert_array_equal(got["nested"][0], m["nested"][0])
            assert got["ints"].dtype == np.int32
            slave.send(("echo", got["ints"]))
        for m in msgs:
            tag, ints = chan.read_on_master()
            assert tag == "echo"
            np.testing.assert_array_equal(ints, m["ints"])
    finally:
        close()


# canonical bytes of _payload() under each wire setting — the GOLDEN
# accounting numbers every transport must report identically.  96 float
# elements (x), 5 float64 (normalized to the codec dtype — float32 even
# on the uncompressed wire), 3 float32 (ones), 4 int32 (never encoded),
# one string flag and FOUR dict keys at the 8-byte scalar rate.
_GOLDEN_BYTES = {
    (None, None): 96 * 4 + 5 * 4 + 3 * 4 + 16 + 8 + 4 * 8,      # 472
    ("fp16", None): 96 * 2 + 5 * 2 + 3 * 2 + 16 + 8 + 4 * 8,    # 264
    ("bf16", None): 96 * 2 + 5 * 2 + 3 * 2 + 16 + 8 + 4 * 8,    # 264
    # int8: each float tensor ships q.nbytes + one 8-byte scale
    (None, "int8"): (96 + 8) + (5 + 8) + (3 + 8) + 16 + 8 + 4 * 8,  # 184
}


@pytest.mark.parametrize("wire_dtype,wire_codec", sorted(
    _GOLDEN_BYTES, key=str
))
def test_nbytes_accounting_identical_across_transports(wire_dtype, wire_codec):
    """The canonical byte counters report the SAME golden number on the
    queue emulation, the real TCP wire and the shm rings — comm_bytes
    is transport-independent — for every codec stage."""
    counted = {}
    for kind in TRANSPORTS:
        chan, slave, close = _make_link(kind, wire_dtype, wire_codec)
        try:
            chan.write_to_slave(_payload())
            slave.recv()
            counted[kind] = chan.bytes_to_slave
        finally:
            close()
    want = _GOLDEN_BYTES[(wire_dtype, wire_codec)]
    assert counted == {kind: want for kind in TRANSPORTS}


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_float64_normalized_to_float32_on_uncompressed_wire(kind):
    """The fp32 (no-codec) wire must not ship 8-byte doubles: float64
    arrays normalize to float32 on write, so ``comm_bytes`` is
    comparable across codec settings (PR 8 accounting-asymmetry fix)."""
    chan, slave, close = _make_link(kind)
    try:
        chan.write_to_slave(np.arange(6, dtype=np.float64))
        got = slave.recv()
        assert got.dtype == np.float32
        assert chan.bytes_to_slave == 6 * 4
        slave.send(np.arange(6, dtype=np.float64))
        back = chan.read_on_master()
        assert back.dtype == np.float32
    finally:
        close()


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_codec_decodes_to_float32_on_read(kind):
    chan, slave, close = _make_link(kind, "fp16")
    try:
        chan.write_to_slave(np.arange(8, dtype=np.float32))
        got = slave.recv()
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, np.arange(8, dtype=np.float32))
        slave.send(got)
        back = chan.read_on_master()
        assert back.dtype == np.float32
    finally:
        close()


def test_tcp_frame_bytes_track_real_wire():
    """TCP additionally accounts what ACTUALLY crossed the socket —
    framing + pickle overhead on top of the canonical payload bytes."""
    chan, slave, close = _make_link("tcp")
    try:
        chan.write_to_slave(_payload())
        slave.recv()
        assert chan.frame_bytes_to_slave > chan.bytes_to_slave > 0
    finally:
        close()


# ---------------------------------------------------------------------------
# shm-specific: segment hygiene and the inline-overflow fallback
# ---------------------------------------------------------------------------


def _shm_segments():
    import os

    try:
        return set(os.listdir("/dev/shm"))
    except (FileNotFoundError, NotADirectoryError):  # pragma: no cover
        pytest.skip("no /dev/shm on this platform")


def test_shm_close_unlinks_every_segment():
    """The shm link creates its rings on open and must leave NOTHING in
    /dev/shm after close — the master owns unlink, the slave only
    detaches."""
    before = _shm_segments()
    chan, slave, close = _make_link("shm")
    try:
        chan.write_to_slave(_payload())
        slave.recv()
        assert _shm_segments() - before  # the rings are real OS segments
    finally:
        close()
    assert _shm_segments() - before == set()


def test_shm_array_larger_than_ring_falls_back_inline():
    """An array that cannot fit the ring ships inline on the control
    socket instead of deadlocking the ring writer — and the canonical
    accounting is unchanged either way."""
    big = np.arange(4096, dtype=np.float32)  # 16 KiB > the 4 KiB ring
    small = np.ones((8, 8), np.float32)
    chan, slave, close = _make_link("shm", ring_bytes=4096)
    try:
        chan.write_to_slave({"big": big, "small": small})
        got = slave.recv()
        np.testing.assert_array_equal(got["big"], big)
        np.testing.assert_array_equal(got["small"], small)
        assert chan.bytes_to_slave == big.nbytes + small.nbytes + 2 * 8
        slave.send(big * 2.0)
        np.testing.assert_array_equal(chan.read_on_master(), big * 2.0)
    finally:
        close()


def test_shm_sustains_many_frames_through_small_ring():
    """Ring reuse under wraparound: far more traffic than the ring's
    capacity crosses intact and in order once the consumer releases."""
    chan, slave, close = _make_link("shm", ring_bytes=1 << 14)
    try:
        msgs = [
            np.full((32, 16), float(i), np.float32)  # 2 KiB each, 64 total
            for i in range(64)
        ]
        def _pump():
            for m in msgs:
                chan.write_to_slave(m)

        t = threading.Thread(target=_pump)
        t.start()
        for m in msgs:
            np.testing.assert_array_equal(slave.recv(), m)
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        close()


# ---------------------------------------------------------------------------
# cluster-level conformance: the same protocol over either wire
# ---------------------------------------------------------------------------


def _ref_conv(x, w):
    import jax

    return np.asarray(jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    ))


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_cluster_forward_matches_reference(kind):
    x = np.random.default_rng(0).normal(size=(2, 8, 8, 3)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(3, 3, 3, 9)).astype(np.float32)
    c = HeteroCluster([1.0, 1.0], transport=kind)
    try:
        c.probe_times = [1.0, 1.0]
        np.testing.assert_allclose(c.conv_forward(x, w), _ref_conv(x, w), atol=1e-4)
    finally:
        c.shutdown()


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_slave_error_propagates_not_hangs(kind):
    """A slave-side exception ships back as a SlaveError and re-raises
    on the master instead of hanging the gather — on either wire.
    (w=None with no cached shard is a guaranteed slave-side KeyError.)"""
    c = HeteroCluster([1.0, 1.0], transport=kind)
    try:
        x = np.zeros((1, 4, 4, 2), np.float32)
        c.sockets[0].write_to_slave(("conv", (x, None)))
        out = c.sockets[0].read_on_master()
        with pytest.raises(RuntimeError, match="slave device 1 failed"):
            c._check_result(out)
        # the link survives the error: the next op still works
        w = np.ones((1, 1, 2, 3), np.float32)
        c.sockets[0].write_to_slave(("conv", (x, w)))
        assert c._check_result(c.sockets[0].read_on_master()).shape == (1, 4, 4, 3)
    finally:
        c.shutdown()


@pytest.mark.parametrize("kind", ["tcp", "shm"])
def test_subprocess_probe_measures_link_bandwidth(kind):
    """probe() on a subprocess transport fills the planning bandwidths
    from a real echo round-trip — the measured link replaces the knob.
    On shm the probe times the RING, so Eq. 1 sees the speed the plans
    will actually get."""
    c = HeteroCluster([1.0, 1.0], transport=kind)
    try:
        c.probe(image_size=8, in_channels=3, kernel_size=3, num_kernels=4,
                batch=2, repeats=1)
        assert all(b is not None and b > 0 for b in c.measured_bandwidths)
        assert c.bandwidths == c.measured_bandwidths
        # the echo probes are not protocol traffic: neither counter family
        # may retain their megabytes
        assert all(s.total_bytes < 1 << 20 for s in c.sockets)
        assert all(
            s.frame_bytes_to_slave + s.frame_bytes_to_master < 1 << 20
            for s in c.sockets
        )
        # RE-probing refreshes the measurement instead of mistaking the
        # first one for a user override
        c.probe(image_size=8, in_channels=3, kernel_size=3, num_kernels=4,
                batch=2, repeats=1)
        assert c.bandwidths == c.measured_bandwidths
        # the comm-aware Eq. 1 consumes it without blowing up
        counts = c.shares_for(16, unit_bytes=1024.0, layer_flops=1e6)
        assert counts.sum() == 16
    finally:
        c.shutdown()


def test_tcp_explicit_bandwidth_overrides_measurement():
    c = HeteroCluster([1.0, 1.0], transport="tcp", bandwidth_mbps=25.0)
    try:
        c.probe(image_size=8, in_channels=3, kernel_size=3, num_kernels=4,
                batch=2, repeats=1)
        assert c.bandwidths == [25.0]
    finally:
        c.shutdown()


@pytest.mark.parametrize("kind", ["tcp", "shm"])
@pytest.mark.parametrize("partition", ["kernel", "spatial", "auto"])
def test_subprocess_train_chain_matches_single_device_vjp(partition, kind):
    """The acceptance bar: the pipelined fwd+bwd train chain over REAL
    subprocess slaves == jax.grad on one device, on every axis and on
    both subprocess wires (tcp sockets and shm rings)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    x = rng.normal(size=(5, 8, 8, 3)).astype(np.float32)
    w1 = rng.normal(size=(3, 3, 3, 6)).astype(np.float32)
    w2 = rng.normal(size=(3, 3, 6, 9)).astype(np.float32)
    g = rng.normal(size=(5, 8, 8, 9)).astype(np.float32)

    def f(x_, w1_, w2_):
        y = jax.nn.relu(jax.lax.conv_general_dilated(
            x_, w1_, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ))
        y2 = jax.lax.conv_general_dilated(
            y, w2_, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
        return jnp.sum(y2 * g)

    dx_want, dw1_want, dw2_want = (
        np.asarray(a)
        for a in jax.grad(f, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2)
        )
    )

    c = HeteroCluster(
        [1.0, 1.0, 1.0], transport=kind, partition=partition,
        pipeline=True, microbatches=3,
        # finite links exercise auto's comm-extended prediction; tcp
        # never delays anything, this only feeds the planner
        bandwidth_mbps=50.0,
    )
    try:
        c.probe_times = [1.0, 1.0, 1.0]

        def between(y):
            mask = (y > 0).astype(np.float32)
            return np.maximum(y, 0.0), lambda gz: gz * mask

        slices = c.microbatch_slices(x.shape[0])

        def head(z, i):
            return None, g[slices[i]]

        res = c.conv_train_chain(x, [w1, w2], [between, None], head)
        np.testing.assert_allclose(res.dx, dx_want, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.dw[0], dw1_want, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.dw[1], dw2_want, rtol=1e-4, atol=1e-3)
    finally:
        c.shutdown()


@pytest.mark.parametrize("kind, member, spec", [
    ("tcp", "numpy", None),
    ("shm", "numpy", None),
    ("inproc", "xla", "acts=fp16,grads=int8"),
    ("tcp", "numpy", "acts=fp16,grads=int8"),
])
def test_device_inputs_cross_the_wire_as_numpy_does(kind, member, spec):
    """The train chain given device arrays, as the device-resident train
    step gives it, over a real wire or a narrowing codec: what crosses
    comes to the host first, the link byte counters read exactly what a
    numpy-input run of the same work reads, the results match that run,
    and on an fp32 wire they match the single-device VJP too."""
    import jax
    import jax.numpy as jnp

    from repro.tracing import to_device

    rng = np.random.default_rng(7)
    x = rng.normal(size=(5, 8, 8, 3)).astype(np.float32)
    w1 = rng.normal(size=(3, 3, 3, 6)).astype(np.float32)
    w2 = rng.normal(size=(3, 3, 6, 9)).astype(np.float32)
    g = rng.normal(size=(5, 8, 8, 9)).astype(np.float32)

    c = HeteroCluster([1.0, 1.0], ["xla", member], transport=kind,
                      wire_codec=spec, pipeline=True, microbatches=3,
                      comp_aware=False)  # both runs split alike
    try:
        c.probe_times = [1.0, 1.0]
        slices = c.microbatch_slices(x.shape[0])

        def run(xp, put):
            def between(y):
                mask = (y > 0).astype(np.float32)
                return xp.maximum(y, 0.0), lambda gz: gz * mask

            gs = [put(g[sl]) for sl in slices]
            c.reset_stats()
            res = c.conv_train_chain(
                put(x), [put(w1), put(w2)], [between, None], lambda z, i: (None, gs[i])
            )
            return res, [(s.bytes_to_slave, s.bytes_to_master) for s in c.sockets]

        host, host_bytes = run(np, np.asarray)
        dev, dev_bytes = run(jnp, to_device)
    finally:
        c.shutdown()
    assert dev_bytes == host_bytes and host_bytes[0][0] > 0
    assert isinstance(dev.dx, jax.Array) and isinstance(host.dx, np.ndarray)
    for a, b in zip([dev.dx] + dev.dw, [host.dx] + host.dw):
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-6, atol=1e-6)
    if spec is None:
        def f(x_, w1_, w2_):
            conv = functools.partial(
                jax.lax.conv_general_dilated, window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            return jnp.sum(conv(jax.nn.relu(conv(x_, w1_)), w2_) * g)

        want = jax.grad(f, argnums=(0, 1, 2))(x, w1, w2)
        for a, b in zip([dev.dx] + dev.dw, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-3)


def test_spawned_slave_is_a_host_cpu_member(monkeypatch):
    """A spawned slave never contends for the master's chip: its
    environment pins JAX_PLATFORMS=cpu whatever the master's says, its
    hello reports the platform it computes on, and a compiled pallas
    slave — which could only run interpreted there — is refused."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # a chip host's setting
    c = HeteroCluster([1.0, 1.0], ["numpy", "xla"], transport="tcp")
    try:
        assert c._slave_env()["JAX_PLATFORMS"] == "cpu"
        assert c.hello_meta[c.slave_ids[0]]["platform"] == "cpu"
    finally:
        c.shutdown()
    with pytest.raises(ValueError, match="host CPU"):
        HeteroCluster([1.0, 1.0], ["numpy", "pallas"], transport="tcp")


@pytest.mark.parametrize("kind", ["tcp", "shm"])
def test_subprocess_orderly_shutdown_reaps_subprocesses(kind):
    c = HeteroCluster([1.0, 1.0, 1.0], transport=kind)
    c.probe_times = [1.0, 1.0, 1.0]
    x = np.zeros((2, 6, 6, 2), np.float32)
    w = np.ones((3, 3, 2, 4), np.float32)
    c.conv_forward(x, w)
    c.shutdown()
    assert [p.returncode for p in c.procs] == [0, 0]
    c.shutdown()  # idempotent


@pytest.mark.parametrize("kind", ["tcp", "shm"])
def test_subprocess_shutdown_after_master_exception_reaps(kind):
    """A protocol error on the master must not leak slave processes:
    shutdown() after the exception still ends them cleanly."""
    c = HeteroCluster([1.0, 1.0], transport=kind)
    try:
        x = np.zeros((1, 4, 4, 2), np.float32)
        c.sockets[0].write_to_slave(("conv", (x, None)))  # slave KeyError
        with pytest.raises(RuntimeError, match="failed"):
            c._check_result(c.sockets[0].read_on_master())
    finally:
        c.shutdown()
    assert [p.returncode for p in c.procs] == [0]
