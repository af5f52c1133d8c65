"""The pipelined FULL training step (conv_train_chain / conv_train_step):
numerics must match the single-device VJP — including mixed compute
backends — the FIFO contract must hold when conv and bwd ops interleave
on the wire, comm bytes must be accounted under emulated bandwidth, and
the documented callback deadlocks must fail fast instead of hanging."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.master_slave import HeteroCluster, make_distributed_conv
from repro.core.partitioner import DeviceProfile, comp_aware_times, profiles_to_shares
from repro.models.cnn import (
    cnn_loss,
    init_cnn,
    make_cluster_train_step,
    make_cnn_config,
)


def _ref_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def _data(b=5, s=8, cin=3, cout=21, k=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, s, s, cin)).astype(np.float32)
    w = rng.normal(size=(k, k, cin, cout)).astype(np.float32)
    g = rng.normal(size=(b, s, s, cout)).astype(np.float32)
    return x, w, g


def _train_chain_refs(x, w1, w2):
    """Single-device forward + VJP of conv -> relu -> conv -> sum(y*g)."""
    _, _, g = _data(b=x.shape[0], s=x.shape[1], cin=x.shape[3],
                    cout=w2.shape[3], seed=9)

    def f(x, w1, w2):
        y = jax.nn.relu(_ref_conv(x, w1))
        return jnp.sum(_ref_conv(y, w2) * g)

    grads = jax.grad(f, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2)
    )
    return g, tuple(np.asarray(a) for a in grads)


def _run_train_chain(cluster, x, w1, w2, g):
    """Drive conv_train_chain with a relu between stage and a fixed-g head."""

    def between(y):
        mask = (y > 0).astype(np.float32)
        return np.maximum(y, 0.0), lambda gz: gz * mask

    slices = cluster.microbatch_slices(x.shape[0])

    def head(z, i):
        return None, g[slices[i]]

    return cluster.conv_train_chain(x, [w1, w2], [between, None], head)


@pytest.mark.parametrize("backends", [None, ["numpy", "xla", "numpy"]])
def test_train_chain_matches_single_device_vjp(backends):
    """Pipelined fwd+bwd over the cluster == jax.grad on one device, for
    all-numpy and mixed numpy/xla clusters (uneven shards, microbatches)."""
    x, w1, _ = _data(cout=6, seed=3)
    rng = np.random.default_rng(4)
    w2 = rng.normal(size=(5, 5, 6, 9)).astype(np.float32)
    g, (dx_want, dw1_want, dw2_want) = _train_chain_refs(x, w1, w2)

    c = HeteroCluster([1.0, 1.5, 2.0], backends, pipeline=True, microbatches=3)
    try:
        c.probe_times = [1.0, 1.5, 2.0]
        res = _run_train_chain(c, x, w1, w2, g)
        np.testing.assert_allclose(res.dx, dx_want, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.dw[0], dw1_want, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.dw[1], dw2_want, rtol=1e-4, atol=1e-3)
    finally:
        c.shutdown()


# (slowdowns, backends) of the clusters the train step is checked on: the
# seed's all-numpy cluster, and the xla master with a host member or a
# member on the chip (pallas, interpreted off a TPU)
TRAIN_STEP_MEMBERS = {
    "numpy": ([1.0, 1.5, 2.0], None),
    "xla-numpy": ([1.0, 1.0], ["xla", "numpy"]),
    "xla-pallas": ([1.0, 1.0], ["xla", "pallas:interpret"]),
}


def _sgd_reference(cfg, params, imgs, labels, lr):
    """Single-device loss and SGD step (jitted: one compile, not one per op)."""
    (loss_ref, _), grads = jax.jit(jax.value_and_grad(
        lambda p: cnn_loss(p, imgs, labels, cfg=cfg), has_aux=True
    ))(params)
    return loss_ref, jax.tree.map(lambda p, g: p - lr * g, params, grads)


def _assert_params_close(ref_new, new_params):
    flat_ref, _ = jax.tree_util.tree_flatten_with_path(ref_new)
    flat_new, _ = jax.tree_util.tree_flatten_with_path(new_params)
    for (pa, a), (_pb, b) in zip(
        sorted(flat_ref, key=lambda kv: str(kv[0])),
        sorted(flat_new, key=lambda kv: str(kv[0])),
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-4, err_msg=str(pa)
        )


@pytest.mark.parametrize("partition", ["kernel", "batch", "spatial"])
@pytest.mark.parametrize("members", list(TRAIN_STEP_MEMBERS))
def test_cluster_train_step_matches_sgd(members, partition):
    """The models/cnn.py driver: one distributed step == loss/grads/SGD of
    the single-device reference, end to end (conv, bias, LRN, pool, fc),
    on every axis and whether the members compute on the host or keep
    their shards on the device."""
    cfg = make_cnn_config(6, 10)
    params = init_cnn(jax.random.key(0), cfg)
    imgs = jax.random.normal(jax.random.key(1), (5, 32, 32, 3))
    labels = jnp.array([0, 1, 2, 3, 4])
    lr = 0.05
    loss_ref, ref_new = _sgd_reference(cfg, params, imgs, labels, lr)

    slowdowns, backends = TRAIN_STEP_MEMBERS[members]
    c = HeteroCluster(slowdowns, backends, pipeline=True, microbatches=3,
                      partition=partition)
    try:
        c.probe(image_size=8, in_channels=3, kernel_size=5, num_kernels=8, batch=2)
        step = make_cluster_train_step(c, cfg, lr=lr)
        new_params, loss, _acc = step(params, imgs, labels)
        assert np.isclose(float(loss_ref), loss, atol=1e-5)
        _assert_params_close(ref_new, new_params)
        assert all(isinstance(a, jax.Array) for a in jax.tree.leaves(new_params))
        # the chain measured the master's non-conv duty for Eq. 1
        assert 0.0 < c.comp_duty <= 1.0
    finally:
        c.shutdown()


@pytest.mark.parametrize("member", ["numpy", "pallas:interpret"])
def test_cluster_train_step_recovers_a_lost_member(member):
    """A member evicted mid-step: the master recomputes its in-flight
    shards from the plans (on the device, for an xla master) and the
    step still equals single-device SGD."""
    cfg = make_cnn_config(4, 6)
    params = init_cnn(jax.random.key(2), cfg)
    imgs = jax.random.normal(jax.random.key(3), (4, 32, 32, 3))
    labels = jnp.array([1, 3, 5, 7])
    lr = 0.05
    loss_ref, ref_new = _sgd_reference(cfg, params, imgs, labels, lr)

    c = HeteroCluster([1.0, 1.0], ["xla", member], pipeline=True, microbatches=2)
    try:
        c.probe_times = [1.0, 1.0]
        gather = c.gather_conv

        def gather_then_evict(p):
            if c.slave_ids:
                c.evict(c.slave_ids[0])
            return gather(p)

        c.gather_conv = gather_then_evict
        new_params, loss, _acc = make_cluster_train_step(c, cfg, lr=lr)(
            params, imgs, labels
        )
        assert c.timing.recompute_s > 0.0 and not c.slave_ids
        assert np.isclose(float(loss_ref), loss, atol=1e-5)
        _assert_params_close(ref_new, new_params)
    finally:
        c.shutdown()


def test_fifo_when_conv_and_bwd_ops_interleave():
    """Interleaved conv/bwd scatters must gather in exact issue order —
    the wire order of a train step — and out-of-order gathers raise."""
    c = HeteroCluster([1.0, 1.5], pipeline=True, microbatches=2)
    try:
        c.probe_times = [1.0, 1.5]
        x, w, g = _data(b=2, seed=6)
        want_y = np.asarray(_ref_conv(x, w))
        _, pullback = jax.vjp(_ref_conv, jnp.asarray(x), jnp.asarray(w))
        dx_want, dw_want = (np.asarray(a) for a in pullback(jnp.asarray(g)))

        p1 = c.scatter_conv(x, w)
        p2 = c.scatter_bwd(x, w, g)
        p3 = c.scatter_conv(x, w)
        # FIFO violations: wrong seq, and wrong op for the right seq
        with pytest.raises(RuntimeError):
            c.gather_bwd(p2)
        with pytest.raises(RuntimeError):
            c.gather_bwd(p1)  # seq 1 is a conv, gathered as bwd
        # draining in issue order still works and stays bit-correct
        np.testing.assert_allclose(c.gather_conv(p1), want_y, atol=1e-4)
        dx, dw = c.gather_bwd(p2)
        np.testing.assert_allclose(dx, dx_want, atol=1e-4)
        np.testing.assert_allclose(dw, dw_want, atol=1e-4)
        np.testing.assert_allclose(c.gather_conv(p3), want_y, atol=1e-4)
    finally:
        c.shutdown()


def test_train_chain_comm_bytes_under_bandwidth():
    """Over finite links the train step's traffic is fully accounted and
    each phase's kernel shard crosses the wire ONCE (microbatches after
    the first ride the slave's cached copy); numerics are unharmed.
    The versioned weight-broadcast cache is disabled so the per-phase
    accounting stays exact (with it on, the bwd phases re-ship their
    unchanged shards as ~24-byte tokens — test_weight_cache.py pins
    that side)."""
    x, w1, _ = _data(b=4, cout=6, seed=3)
    rng = np.random.default_rng(4)
    w2 = rng.normal(size=(5, 5, 6, 9)).astype(np.float32)
    g, (dx_want, dw1_want, dw2_want) = _train_chain_refs(x, w1, w2)

    c = HeteroCluster([1.0, 1.0], pipeline=True, microbatches=4,
                      bandwidth_mbps=2000.0, weight_cache=False)
    try:
        c.probe_times = [1.0, 1.0]
        c.reset_stats()
        # the counts the chain will use: compute BEFORE the run — the
        # chain's measured comp_duty re-balances shares for LATER steps
        counts = [c.shares_for(w.shape[-1]) for w in (w1, w2)]
        res = _run_train_chain(c, x, w1, w2, g)
        np.testing.assert_allclose(res.dx, dx_want, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.dw[1], dw2_want, rtol=1e-4, atol=1e-3)
        shard_b = [c._split(w, ct)[1].nbytes for w, ct in ((w1, counts[0]), (w2, counts[1]))]
        y1 = np.maximum(np.asarray(_ref_conv(x, w1)), 0.0)
        # master -> slave, per phase: fwd k sends x_k per microbatch + its
        # shard once; bwd k sends (x_k, g_k-slice) per microbatch + the
        # shard once.  Everything else is 8-byte flags/None markers.
        g2_slave = g.nbytes // g.shape[-1] * int(counts[1][1])
        g1_slave = y1.nbytes // y1.shape[-1] * int(counts[0][1])
        payload = (
            x.nbytes + shard_b[0]                 # fwd conv1
            + y1.nbytes + shard_b[1]              # fwd conv2
            + y1.nbytes + shard_b[1] + g2_slave   # bwd conv2
            + x.nbytes + shard_b[0] + g1_slave    # bwd conv1
        )
        to_slave = c.sockets[0].bytes_to_slave
        assert payload <= to_slave <= payload + 1024, (payload, to_slave)
        assert c.comm_bytes == sum(s.total_bytes for s in c.sockets)
        assert c.sockets[0].bytes_to_master > 0
    finally:
        c.shutdown()


def test_callback_deadlocks_fail_fast():
    """The two documented make_distributed_conv deadlocks raise a clear
    error at construction instead of hanging at 0% CPU."""
    c = HeteroCluster([1.0, 1.0], ["xla", "numpy"])
    try:
        with pytest.raises(RuntimeError, match="master.*numpy"):
            make_distributed_conv(c)
    finally:
        c.shutdown()

    # plain "pallas" is compiled TPU code: off a TPU the cluster refuses
    # to build it, so it can never reach the callbacks interpreted; on a
    # TPU the compiled slave passes the guard
    if jax.devices()[0].platform != "tpu":
        with pytest.raises(RuntimeError, match="pallas:interpret"):
            HeteroCluster([1.0, 1.0], ["numpy", "pallas"])
    else:
        c = HeteroCluster([1.0, 1.0], ["numpy", "pallas"])
        try:
            make_distributed_conv(c)
        finally:
            c.shutdown()

    # the parameterized registry name must not slip past the check
    c = HeteroCluster([1.0, 1.0], ["numpy", "pallas:interpret"])
    try:
        with pytest.raises(RuntimeError, match="interpret"):
            make_distributed_conv(c)
    finally:
        c.shutdown()


def test_comp_aware_shares_discount_master():
    """A busy master (non-conv duty) loses conv kernels to the slaves;
    comp_aware=False restores the seed behaviour."""
    c = HeteroCluster([1.0, 1.0, 1.0])
    try:
        c.probe_times = [1.0, 1.0, 1.0]
        base = c.shares_for(30).tolist()
        c.comp_duty = 0.5
        discounted = c.shares_for(30).tolist()
        assert discounted[0] < base[0]
        assert sum(discounted) == 30
        c.comp_aware = False
        assert c.shares_for(30).tolist() == base
    finally:
        c.shutdown()

    t = comp_aware_times([1.0, 2.0], 0.5)
    assert t[0] == pytest.approx(2.0) and t[1] == pytest.approx(2.0)
    # duty >= 1 clamps instead of dividing by zero
    assert np.isfinite(comp_aware_times([1.0], 1.0)[0])

    profs = [DeviceProfile("m", 1.0, comp_duty=0.5), DeviceProfile("s", 1.0)]
    shares = profiles_to_shares(profs)
    assert shares[0] == pytest.approx(1.0 / 3.0)
    assert profs[0].with_comp_duty(0.0).effective_conv_time == pytest.approx(1.0)


def test_zero_kernel_shard_runs_on_every_backend():
    """Comp-aware shares may allocate 0 kernels to a device; the protocol
    must tolerate that on any backend (pallas grid math divides by cout),
    both directions — instead of killing the slave and hanging."""
    x, w, g = _data(b=2, s=4, cout=4, k=3, seed=10)
    # pallas-interpret slave deliberately given ~no share via probe times
    c = HeteroCluster([1.0, 1e6], ["numpy", "pallas:interpret"])
    try:
        c.probe_times = [1.0, 1e6]
        assert c.shares_for(4).tolist() == [4, 0]
        want = np.asarray(_ref_conv(x, w))
        np.testing.assert_allclose(c.conv_forward(x, w), want, atol=1e-4)
        _, pullback = jax.vjp(_ref_conv, jnp.asarray(x), jnp.asarray(w))
        dx_want, dw_want = pullback(jnp.asarray(g))
        dx, dw = c.conv_backward(x, w, g)
        np.testing.assert_allclose(dx, np.asarray(dx_want), atol=1e-4)
        np.testing.assert_allclose(dw, np.asarray(dw_want), atol=1e-4)
    finally:
        c.shutdown()


def test_slave_exception_raises_at_gather():
    """A slave whose backend blows up ships the traceback to the master,
    which raises at the matching gather — no 0%-CPU hang."""
    from repro.core.cluster.plans import LayerPlan

    x, w, _ = _data(b=2, s=4, cout=4, k=3, seed=11)
    c = HeteroCluster([1.0, 1.0])
    try:
        c.probe_times = [1.0, 1.0]
        plan = LayerPlan(
            "kernel", np.array([2, 2]),
            shards=[w[..., :2], "not-an-array"],
            member_ids=tuple(c.slave_ids),
        )
        p = c._scatter_conv_shards(x, plan, send_weights=True)
        with pytest.raises(RuntimeError, match="slave device 1 failed"):
            c.gather_conv(p)
    finally:
        c.shutdown()


def test_mesh_context_compat():
    """The version-compat mesh shim activates a mesh visible to the
    sharding constraints on every pinned jax (the seed-failure bugfix)."""
    from repro.compat import get_active_mesh, mesh_context

    assert get_active_mesh() is None
    mesh = jax.make_mesh((1,), ("model",))
    with mesh_context(mesh):
        active = get_active_mesh()
        assert active is not None
        assert "model" in active.axis_names
    assert get_active_mesh() is None
