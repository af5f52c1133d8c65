"""The backend contract: every registered compute backend must produce
the same conv / conv_vjp results (numpy ≡ xla ≡ pallas-interpret), and a
mixed-backend HeteroCluster must match the single-device reference model
end to end — the probe, the slaves, and the master time the same code
they run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import (
    available_backends,
    get_backend,
    make_conv_fn,
    probe_conv_time,
)
from repro.core.master_slave import HeteroCluster, make_distributed_conv
from repro.models.cnn import cnn_loss, init_cnn, make_cnn_config

# interpret mode by name (plain "pallas" needs a TPU); the id keeps the
# test names the backend's
PARITY_BACKENDS = ["numpy", "xla", pytest.param("pallas:interpret", id="pallas")]


def _ref_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def _data(b=2, s=8, cin=3, cout=7, k=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, s, s, cin)).astype(np.float32)
    w = rng.normal(size=(k, k, cin, cout)).astype(np.float32)
    g = rng.normal(size=(b, s, s, cout)).astype(np.float32)
    return x, w, g


def test_registry_exposes_the_contract():
    assert {"numpy", "xla", "pallas", "sim"} <= set(available_backends())
    with pytest.raises(KeyError):
        get_backend("no-such-backend")


def test_plain_pallas_refuses_to_interpret_silently():
    """Off a TPU, plain "pallas" raises instead of falling back to
    interpret mode; interpret mode runs only when asked for by name."""
    if jax.devices()[0].platform == "tpu":
        pytest.skip("plain pallas is the compiled kernel on a TPU")
    with pytest.raises(RuntimeError, match="pallas:interpret"):
        get_backend("pallas")
    with pytest.raises(RuntimeError, match="pallas:interpret"):
        get_backend("pallas:compiled")
    with pytest.raises(RuntimeError, match="interpret=True"):
        make_conv_fn("pallas")
    with pytest.raises(RuntimeError, match="pallas:interpret"):
        HeteroCluster([1.0, 1.0], ["numpy", "pallas"])
    assert get_backend("pallas:interpret").interpret
    make_conv_fn("pallas", interpret=True)


def test_registry_parameterized_instances():
    """"sim:5e9" is a 5 GFLOP/s sim device, cached per parameterized
    name: a cluster can mix sim speeds without the slowdown workaround."""
    fast = get_backend("sim:5e9")
    slow = get_backend("sim:1e9")
    assert fast is not slow
    assert fast.flops_per_s == pytest.approx(5e9)
    assert slow.flops_per_s == pytest.approx(1e9)
    assert get_backend("sim:5e9") is fast  # each name caches its own
    assert get_backend("sim") is not fast
    with pytest.raises(ValueError, match="rejected parameter"):
        get_backend("sim:not-a-number")
    with pytest.raises(ValueError, match="rejected parameter"):
        get_backend("sim:-1e9")
    with pytest.raises(KeyError):
        get_backend("no-such-backend:5e9")


def test_parameterized_sim_cluster_shares():
    """Two sim devices at different registry-parameter speeds probe at
    ~the speed ratio, so Eq. 1 splits accordingly — no slowdown needed."""
    c = HeteroCluster([1.0, 1.0], ["sim:4e9", "sim:1e9"])
    try:
        # sleeps of ~2.5/10 ms: far above the host's timer slack
        t = c.probe(image_size=16, in_channels=3, kernel_size=5,
                    num_kernels=32, batch=8, repeats=1)
        assert t[1] > 2.0 * t[0]  # 4x nominal; sleep jitter-safe margin
        counts = c.shares_for(20)
        assert counts[0] > counts[1]
    finally:
        c.shutdown()


@pytest.mark.parametrize("name", PARITY_BACKENDS)
def test_conv_parity(name):
    x, w, _ = _data()
    got = get_backend(name).conv(x, w)
    want = np.asarray(_ref_conv(x, w))
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("name", PARITY_BACKENDS)
def test_conv_vjp_parity(name):
    x, w, g = _data(seed=1)
    _, pullback = jax.vjp(_ref_conv, jnp.asarray(x), jnp.asarray(w))
    dx_want, dw_want = pullback(jnp.asarray(g))
    dx, dw = get_backend(name).conv_vjp(x, w, g)
    np.testing.assert_allclose(dx, np.asarray(dx_want), atol=1e-4)
    np.testing.assert_allclose(dw, np.asarray(dw_want), atol=1e-4)


def test_even_kernel_backends_self_consistent():
    """Even kernels: numpy and pallas share the repo's k//2-low SAME pad
    (XLA's differs), so they must agree with each other."""
    x, w, g = _data(cout=6, k=4, seed=2)
    np_b, pl_b = get_backend("numpy"), get_backend("pallas:interpret")
    np.testing.assert_allclose(pl_b.conv(x, w), np_b.conv(x, w), atol=1e-4)
    dx_n, dw_n = np_b.conv_vjp(x, w, g)
    dx_p, dw_p = pl_b.conv_vjp(x, w, g)
    np.testing.assert_allclose(dx_p, dx_n, atol=1e-4)
    np.testing.assert_allclose(dw_p, dw_n, atol=1e-4)


@pytest.mark.parametrize("name", ["numpy", "xla", "sim"])
def test_probe_times_every_backend(name):
    t = probe_conv_time(name, image_size=8, in_channels=3, kernel_size=3,
                        num_kernels=4, batch=2, repeats=1)
    assert t > 0


def test_probe_slowdown_scales_measurement():
    """The emulated slowdown multiplies the measured median — in BOTH
    directions: a slowdown < 1 emulates a FASTER device and must shrink
    the probe time too (it used to be silently dropped, handing emulated
    fast devices an unscaled time and the wrong Eq. 1 share).  200x
    factors dwarf scheduler noise on a loaded CI host, so the ordering
    is safe to assert (per-backend ordering at small factors is not)."""
    kw = dict(image_size=8, in_channels=3, kernel_size=3,
              num_kernels=4, batch=2, repeats=1)
    base = probe_conv_time("numpy", **kw)
    slowed = probe_conv_time("numpy", slowdown=200.0, **kw)
    assert slowed > base
    sped = probe_conv_time("numpy", slowdown=1.0 / 200.0, **kw)
    assert sped < base
    with pytest.raises(ValueError, match="positive"):
        probe_conv_time("numpy", slowdown=0.0, **kw)


def test_sim_probe_slowdown_below_one_exact():
    """On the deterministic sim backend the scaling is exact: the probe
    at slowdown s is ~s x the unscaled probe (the Eq. 1 input an
    emulated faster device must present)."""
    kw = dict(image_size=16, in_channels=3, kernel_size=5,
              num_kernels=16, batch=8, repeats=1)  # ~5 ms sleeps
    base = probe_conv_time("sim", **kw)
    fast = probe_conv_time("sim", slowdown=0.25, **kw)
    assert fast == pytest.approx(0.25 * base, rel=0.2)


def test_sim_backend_shapes_only():
    x, w, g = _data()
    sim = get_backend("sim")
    assert sim.conv(x, w).shape == (2, 8, 8, 7)
    dx, dw = sim.conv_vjp(x, w, g)
    assert dx.shape == x.shape and dw.shape == w.shape


@pytest.mark.parametrize("name", PARITY_BACKENDS)
def test_make_conv_fn_grads_match_reference(name):
    """The jax-level conv_fn of each backend is differentiable and
    matches lax end to end (forward + grads, bias included)."""
    rng = np.random.default_rng(3)
    params = {
        "kernel": jnp.asarray(rng.normal(size=(3, 3, 2, 5)).astype(np.float32)),
        "bias": jnp.asarray(rng.normal(size=(5,)).astype(np.float32)),
    }
    x = jnp.asarray(rng.normal(size=(2, 6, 6, 2)).astype(np.float32))
    from repro.layers.conv import apply_conv

    conv_fn = make_conv_fn(name)

    def loss(fn, p, xx):
        return jnp.sum(fn(p, xx) ** 2)

    ref = loss(apply_conv, params, x)
    got = loss(conv_fn, params, x)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-4)
    g_ref = jax.grad(lambda p: loss(apply_conv, p, x))(params)
    g_got = jax.grad(lambda p: loss(conv_fn, p, x))(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-3)


@pytest.fixture(scope="module")
def mixed_cluster():
    """Heterogeneous cluster where every device runs a DIFFERENT backend:
    numpy master (callback-safe), xla + pallas-interpret slaves."""
    c = HeteroCluster([1.0, 1.5, 2.0], ["numpy", "xla", "pallas:interpret"])
    c.probe(image_size=8, in_channels=3, kernel_size=5, num_kernels=8, batch=2)
    yield c
    c.shutdown()


def test_mixed_cluster_forward_matches_reference(mixed_cluster):
    x, w, _ = _data(s=16, cout=21, seed=4)  # odd count: uneven shards
    got = mixed_cluster.conv_forward(x, w)
    np.testing.assert_allclose(got, np.asarray(_ref_conv(x, w)), atol=1e-4)


def test_mixed_cluster_backward_matches_reference(mixed_cluster):
    x, w, g = _data(s=16, cout=21, seed=5)
    _, pullback = jax.vjp(_ref_conv, jnp.asarray(x), jnp.asarray(w))
    dx_want, dw_want = pullback(jnp.asarray(g))
    dx, dw = mixed_cluster.conv_backward(x, w, g)
    np.testing.assert_allclose(dx, np.asarray(dx_want), atol=1e-4)
    np.testing.assert_allclose(dw, np.asarray(dw_want), atol=1e-4)


def test_mixed_cluster_end_to_end_cnn():
    """Full CNN loss + grads through a mixed-backend distributed conv
    must equal the local single-device model.  numpy master + xla slaves:
    pallas-INTERPRET slaves can deadlock when compiling inside the window
    where the master blocks in a jax host callback (interpret mode
    re-enters jax); the direct-call protocol tests above cover pallas."""
    cluster = HeteroCluster([1.0, 1.5, 2.0], ["numpy", "xla", "xla"])
    cluster.probe(image_size=8, in_channels=3, kernel_size=5,
                  num_kernels=8, batch=2)
    try:
        _check_cnn_end_to_end(cluster)
    finally:
        cluster.shutdown()


def _check_cnn_end_to_end(cluster):
    cfg = make_cnn_config(6, 10)
    params = init_cnn(jax.random.key(0), cfg)
    imgs = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    labels = jnp.array([0, 1, 2, 3])
    dist_conv = make_distributed_conv(cluster)

    loss_ref, _ = cnn_loss(params, imgs, labels, cfg=cfg)
    loss_dist, _ = cnn_loss(params, imgs, labels, cfg=cfg, conv_fn=dist_conv)
    assert np.isclose(float(loss_ref), float(loss_dist), atol=1e-5)

    g_ref = jax.grad(lambda p: cnn_loss(p, imgs, labels, cfg=cfg)[0])(params)
    g_dist = jax.grad(
        lambda p: cnn_loss(p, imgs, labels, cfg=cfg, conv_fn=dist_conv)[0]
    )(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_dist)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)


def test_probe_reflects_backend_not_just_device(mixed_cluster):
    """Eq. 1 input: every entry positive, one per device."""
    assert len(mixed_cluster.probe_times) == 3
    assert all(t > 0 for t in mixed_cluster.probe_times)
    counts = mixed_cluster.shares_for(64)
    assert counts.sum() == 64 and (counts >= 0).all()
