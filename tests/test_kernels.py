"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (the kernel bodies execute in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.conv2d import conv2d_dw_pallas, conv2d_dx_pallas, conv2d_pallas
from repro.kernels.flash_attn import flash_attention_pallas
from repro.kernels.ssd import ssd_pallas

ATOL = {jnp.float32: 2e-4, jnp.bfloat16: 5e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,w,cin,cout,k",
    [
        (1, 8, 8, 3, 16, 3),
        (2, 16, 16, 8, 24, 5),   # odd cout vs tile
        (2, 32, 32, 3, 50, 5),   # the paper's C1 layer (reduced batch)
        (1, 16, 16, 50, 40, 5),
    ],
)
def test_conv2d_sweep(b, h, w, cin, cout, k, dtype):
    x = jax.random.normal(jax.random.key(0), (b, h, w, cin), jnp.float32).astype(dtype)
    wk = (jax.random.normal(jax.random.key(1), (k, k, cin, cout), jnp.float32) * 0.1).astype(dtype)
    got = conv2d_pallas(x, wk, cout_tile=16, interpret=True)
    want = ref.conv2d_ref(x.astype(jnp.float32), wk.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=ATOL[dtype], rtol=0.05
    )


@pytest.mark.parametrize("cin,cout", [(40, 24), (24, 40)])
def test_conv2d_fwd_dx_dw_over_contract_tiles(cin, cout):
    """Channels wider than one tile: forward and dX sum over several
    zero-padded contract tiles through the VMEM accumulator, dW tiles Cin
    as well as Cout — each must still equal the lax conv and its VJP."""
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (2, 8, 8, cin), jnp.float32)
    wk = jax.random.normal(ks[1], (3, 3, cin, cout), jnp.float32) * 0.1
    g = jax.random.normal(ks[2], (2, 8, 8, cout), jnp.float32)
    y_want, pullback = jax.vjp(ref.conv2d_ref, x, wk)
    dx_want, dw_want = pullback(g)
    tiles = dict(contract_tile=16, interpret=True)
    y = conv2d_pallas(x, wk, cout_tile=16, **tiles)
    dx = conv2d_dx_pallas(g, wk, cin_tile=16, **tiles)
    dw = conv2d_dw_pallas(x, g, 3, 3, cout_tile=16, cin_tile=16, interpret=True)
    tol = dict(atol=ATOL[jnp.float32], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want), **tol)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_want), **tol)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_want), atol=1e-3, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("s,t,d", [(32, 32, 16), (48, 80, 32), (17, 33, 8)])
def test_flash_attention_sweep(s, t, d, causal, window, dtype):
    if t < s:
        pytest.skip("kv shorter than q not in the contract")
    q = jax.random.normal(jax.random.key(0), (2, 2, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.key(1), (2, 2, t, d), jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.key(2), (2, 2, t, d), jnp.float32).astype(dtype)
    got = flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=16, block_k=16,
        interpret=True,
    )
    want = ref.flash_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=causal, window=window,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=ATOL[dtype], rtol=0.05
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,p,n,chunk", [(32, 2, 8, 4, 8), (48, 3, 16, 8, 16), (25, 1, 4, 4, 8)])
def test_ssd_sweep(s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (2, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (2, s, h, n), jnp.float32)
    cm = jax.random.normal(ks[4], (2, s, h, n), jnp.float32)
    got = ssd_pallas(
        x.astype(dtype), dt, a, bm.astype(dtype), cm.astype(dtype),
        chunk=chunk, interpret=True,
    )
    want, _ = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want),
        atol=10 * ATOL[dtype], rtol=0.05,
    )


def test_flash_matches_model_attention_path():
    """The kernel and the model's blockwise path implement the same
    contract (right-aligned decode positions)."""
    from repro.layers.attention import blockwise_attention

    b, s, t, h, d = 1, 8, 24, 2, 16
    q = jax.random.normal(jax.random.key(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (b, t, h, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (b, t, h, d), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
    kv_pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    want = blockwise_attention(q, k, v, q_pos, kv_pos, causal=True, window=None, block_k=8)
    got = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, block_q=8, block_k=8, interpret=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
