"""The asynchronous pipelined protocol must be bit-compatible with the
barrier protocol (and the local reference) — double-buffered microbatch
scatter/gather, the layer chain, bandwidth-limited links, and the FIFO
ordering contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.master_slave import HeteroCluster, make_distributed_conv
from repro.models.cnn import cnn_loss, init_cnn, make_cnn_config


def _ref_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


@pytest.fixture(scope="module")
def pipelined():
    """Pipelined hetero cluster; batch 5 over 3 microbatches exercises
    uneven microbatch sizes on top of uneven kernel shards."""
    c = HeteroCluster([1.0, 1.5, 2.0], pipeline=True, microbatches=3)
    c.probe(image_size=8, in_channels=3, kernel_size=5, num_kernels=8, batch=2)
    yield c
    c.shutdown()


def _data(b=5, s=8, cin=3, cout=21, k=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, s, s, cin)).astype(np.float32)
    w = rng.normal(size=(k, k, cin, cout)).astype(np.float32)
    g = rng.normal(size=(b, s, s, cout)).astype(np.float32)
    return x, w, g


def test_pipelined_forward_matches_reference(pipelined):
    x, w, _ = _data()
    got = pipelined.conv_forward(x, w)
    np.testing.assert_allclose(got, np.asarray(_ref_conv(x, w)), atol=1e-4)


def test_pipelined_backward_matches_reference(pipelined):
    x, w, g = _data(seed=1)
    _, pullback = jax.vjp(_ref_conv, jnp.asarray(x), jnp.asarray(w))
    dx_want, dw_want = pullback(jnp.asarray(g))
    dx, dw = pipelined.conv_backward(x, w, g)
    np.testing.assert_allclose(dx, np.asarray(dx_want), atol=1e-4)
    np.testing.assert_allclose(dw, np.asarray(dw_want), atol=1e-4)


@pytest.mark.parametrize("entry", ["conv_forward", "conv_backward", "serve_chain"])
def test_numpy_callers_get_numpy_back_from_chip_members(entry):
    """Members that keep their shards on the device (an xla master and a
    pallas member): the gathers assemble there, and a numpy caller of
    the public edge still gets numpy back, equal to the reference."""
    from repro import tracing
    from repro.core.cluster.scheduler import ServeChain

    x, w, g = _data(b=3, cout=6, seed=3)
    _, pullback = jax.vjp(_ref_conv, jnp.asarray(x), jnp.asarray(w))
    c = HeteroCluster([1.0, 1.0], ["xla", "pallas:interpret"], pipeline=True,
                      microbatches=2)
    try:
        c.probe_times = [1.0, 1.0]
        before = tracing.counters()
        if entry == "conv_forward":
            got, want = [c.conv_forward(x, w)], [_ref_conv(x, w)]
        elif entry == "conv_backward":
            got, want = list(c.conv_backward(x, w, g)), list(pullback(jnp.asarray(g)))
        else:
            chain = ServeChain(c, [w])
            assert chain.push(x) is None
            got, want = [chain.flush()], [_ref_conv(x, w)]
        after = tracing.counters()
    finally:
        c.shutdown()
    for a, b in zip(got, want):
        assert type(a) is np.ndarray
        np.testing.assert_allclose(a, np.asarray(b), atol=1e-4)
    assert after["device_assembles"] > before["device_assembles"]
    assert after["host_assembles"] == before["host_assembles"]


def test_single_image_degenerates_to_barrier(pipelined):
    """batch < microbatches: no empty microbatches, same numerics."""
    x, w, _ = _data(b=1, seed=2)
    got = pipelined.conv_forward(x, w)
    np.testing.assert_allclose(got, np.asarray(_ref_conv(x, w)), atol=1e-4)


def test_forward_chain_matches_sequential(pipelined):
    """2-layer conv chain with master-only between stages == running the
    layers sequentially on the reference."""
    x, w1, _ = _data(cout=6, seed=3)
    rng = np.random.default_rng(4)
    w2 = rng.normal(size=(5, 5, 6, 9)).astype(np.float32)

    def between(y):
        return np.maximum(y, 0.0)[:, ::2, ::2, :]

    got = pipelined.conv_forward_chain(x, [w1, w2], [between, None])
    ref1 = between(np.asarray(_ref_conv(x, w1)))
    want = np.asarray(_ref_conv(ref1, w2))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_overlap_is_accounted(pipelined):
    pipelined.reset_stats()
    x, w, _ = _data(seed=5)
    pipelined.conv_forward(x, w)
    t = pipelined.timing
    assert t.overlap_s > 0.0          # scatters were in flight during gathers
    assert t.gather_wait_s >= 0.0
    assert t.comm_s > 0.0


def test_gather_order_is_enforced(pipelined):
    """The FIFO sockets make out-of-order gathers a protocol violation."""
    x, w, _ = _data(b=2, seed=6)
    p1 = pipelined.scatter_conv(x, w)
    p2 = pipelined.scatter_conv(x, w)
    with pytest.raises(RuntimeError):
        pipelined.gather_conv(p2)
    # the failed gather read nothing: draining in order still works
    pipelined.gather_conv(p1)
    pipelined.gather_conv(p2)


def test_bandwidth_limited_links_preserve_numerics():
    """Finite emulated links delay delivery, never corrupt it."""
    c = HeteroCluster([1.0, 1.0], pipeline=True, microbatches=2,
                      bandwidth_mbps=2000.0)
    try:
        c.probe_times = [1.0, 1.0]
        x, w, g = _data(b=4, seed=7)
        np.testing.assert_allclose(
            c.conv_forward(x, w), np.asarray(_ref_conv(x, w)), atol=1e-4
        )
        _, pullback = jax.vjp(_ref_conv, jnp.asarray(x), jnp.asarray(w))
        dx_want, dw_want = pullback(jnp.asarray(g))
        dx, dw = c.conv_backward(x, w, g)
        np.testing.assert_allclose(dx, np.asarray(dx_want), atol=1e-4)
        np.testing.assert_allclose(dw, np.asarray(dw_want), atol=1e-4)
        assert c.comm_bytes > 0
    finally:
        c.shutdown()


def test_pipelined_weight_traffic_sent_once():
    """Pipelined microbatches send each layer's kernel shard ONCE; later
    microbatches carry w=None and the slave reuses its cached shard."""
    c = HeteroCluster([1.0, 1.0], pipeline=True, microbatches=4)
    try:
        c.probe_times = [1.0, 1.0]
        x, w, _ = _data(b=8, seed=8)
        c.reset_stats()
        got = c.conv_forward(x, w)
        np.testing.assert_allclose(got, np.asarray(_ref_conv(x, w)), atol=1e-4)
        shard_bytes = c._split(w, c.shares_for(w.shape[-1]))[1].nbytes
        to_slave = c.sockets[0].bytes_to_slave
        # all 4 microbatch inputs + ONE shard (+ a few 8-byte flags);
        # resending the shard per microbatch would add 3*shard_bytes
        assert to_slave < x.nbytes + 2 * shard_bytes
        assert to_slave >= x.nbytes + shard_bytes
    finally:
        c.shutdown()


def test_pipelined_end_to_end_cnn_gradients(pipelined):
    """Full CNN through the pipelined cluster via jax callbacks == local."""
    cfg = make_cnn_config(6, 10)
    params = init_cnn(jax.random.key(0), cfg)
    imgs = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    labels = jnp.array([0, 1, 2, 3])
    dist_conv = make_distributed_conv(pipelined)

    loss_ref, _ = cnn_loss(params, imgs, labels, cfg=cfg)
    loss_dist, _ = cnn_loss(params, imgs, labels, cfg=cfg, conv_fn=dist_conv)
    assert np.isclose(float(loss_ref), float(loss_dist), atol=1e-5)

    g_ref = jax.grad(lambda p: cnn_loss(p, imgs, labels, cfg=cfg)[0])(params)
    g_dist = jax.grad(
        lambda p: cnn_loss(p, imgs, labels, cfg=cfg, conv_fn=dist_conv)[0]
    )(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_dist)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)
