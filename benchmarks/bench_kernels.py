"""Kernel microbenchmarks.

On CPU the Pallas kernels run in interpret mode (Python), so wall-times
are NOT kernel performance — we time the pure-jnp references as the host
baseline and report each kernel's FLOP count + arithmetic intensity +
the v5e roofline-predicted time (the kernel-level §Roofline terms)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.roofline.analysis import HW


def _time(f, *args, reps=3):
    f(*args)
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps


def _bench_backends(rows, smoke: bool):
    """Conv backend comparison through the registry contract — the same
    code the cluster's devices run (core/backends.py)."""
    from repro.core.backends import get_backend

    rng = np.random.default_rng(0)
    b, s, cin, cout = (2, 8, 4, 16) if smoke else (8, 32, 3, 64)
    x = rng.normal(size=(b, s, s, cin)).astype(np.float32)
    w = rng.normal(size=(5, 5, cin, cout)).astype(np.float32)
    g = rng.normal(size=(b, s, s, cout)).astype(np.float32)
    flops = 2 * b * s * s * 25 * cin * cout
    for name in ("numpy", "xla"):
        bk = get_backend(name)
        dt = _time(bk.conv, x, w)
        dtv = _time(lambda *a: bk.conv_vjp(*a), x, w, g)
        rows.append((
            f"backend_conv_{name}", dt * 1e6,
            f"host_gflops={flops / dt / 1e9:.2f} vjp_us={dtv * 1e6:.0f}",
        ))

    # the numpy forward's hot path.  Copy-free formulations of the k>1
    # conv (tensordot/einsum on the strided window view, per-tap shifted
    # GEMMs) all measured SLOWER than the single large im2col GEMM —
    # tensordot materializes the same copy internally — so the copy
    # stays only where the GEMM genuinely needs it, and 1x1 kernels skip
    # the lowering entirely: one GEMM on a free reshape, no pad, no
    # window copy.  This row times that lowering-free path against
    # forcing the same shape through im2col.
    from repro.core.backends import _im2col, numpy_conv

    def _im2col_conv(xx, ww):
        kh, kw, cin_, cout_ = ww.shape
        cols = _im2col(np.asarray(xx, np.float32), kh, kw)
        y = cols.reshape(-1, kh * kw * cin_) @ ww.reshape(kh * kw * cin_, cout_)
        return y.reshape(xx.shape[0], xx.shape[1], xx.shape[2], cout_)

    bm, sm, cm = (2, 8, 16) if smoke else (8, 32, 64)
    xm = rng.normal(size=(bm, sm, sm, cm)).astype(np.float32)
    wm = rng.normal(size=(1, 1, cm, 2 * cm)).astype(np.float32)
    dt_new = min(_time(numpy_conv, xm, wm, reps=5) for _ in range(3))
    dt_old = min(_time(_im2col_conv, xm, wm, reps=5) for _ in range(3))
    rows.append((
        "numpy_fwd_1x1_nocopy", dt_new * 1e6,
        f"im2col_us={dt_old * 1e6:.0f} "
        f"gain={dt_old / dt_new:.2f}x (>1 means the lowering-free 1x1 "
        f"GEMM beats forcing the im2col window copy)",
    ))
    # pallas in interpret mode (Python on the CPU): tiny shape, parity
    # timing only — kernel perf is only meaningful on a real TPU
    xt = x[:1, :8, :8, :2].copy()
    wt = w[:, :, :2, :8].copy()
    gt = g[:1, :8, :8, :8].copy()
    bk = get_backend("pallas:interpret")
    dt = _time(bk.conv, xt, wt)
    dtv = _time(lambda *a: bk.conv_vjp(*a), xt, wt, gt)
    rows.append((
        "backend_conv_pallas_interpret_tiny", dt * 1e6,
        f"vjp_us={dtv * 1e6:.0f} (interpret mode; not kernel perf)",
    ))


def run(smoke: bool = False):
    rows = []
    jit = jax.jit

    _bench_backends(rows, smoke)
    if smoke:
        return rows

    # conv2d: the paper's C2 layer geometry (16x16x500 -> 1500 kernels)
    x = jax.random.normal(jax.random.key(0), (8, 16, 16, 500), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (5, 5, 500, 1500), jnp.float32)
    dt = _time(jit(ref.conv2d_ref), x, w)
    flops = 2 * 8 * 16 * 16 * 1500 * 5 * 5 * 500
    byts = (x.size + w.size + 8 * 16 * 16 * 1500) * 4
    rows.append((
        "kernel_conv2d_c2layer", dt * 1e6,
        f"gflop={flops/1e9:.1f} AI={flops/byts:.0f} "
        f"v5e_pred={max(flops/HW.peak_flops, byts/HW.hbm_bw)*1e6:.0f}us "
        f"host_gflops={flops/dt/1e9:.1f}",
    ))

    # flash attention: one 32k-context decode-shape head block
    q = jax.random.normal(jax.random.key(2), (1, 8, 128, 128), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(3), (1, 8, 4096, 128), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(4), (1, 8, 4096, 128), jnp.bfloat16)
    dt = _time(jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True)), q, k, v)
    flops = 2 * 2 * 8 * 128 * 4096 * 128
    byts = (q.size + k.size + v.size + q.size) * 2
    rows.append((
        "kernel_flash_attn_4k", dt * 1e6,
        f"gflop={flops/1e9:.2f} AI={flops/byts:.0f} "
        f"v5e_pred={max(flops/HW.peak_flops, byts/HW.hbm_bw)*1e6:.0f}us",
    ))

    # ssd: mamba2-370m one layer at 4k seq
    B, S, H, P, N = 1, 4096, 32, 64, 128
    xs = jax.random.normal(jax.random.key(5), (B, S, H, P), jnp.float32)
    dts = jax.nn.softplus(jax.random.normal(jax.random.key(6), (B, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.key(7), (H,)))
    bm = jax.random.normal(jax.random.key(8), (B, S, H, N), jnp.float32)
    cm = jax.random.normal(jax.random.key(9), (B, S, H, N), jnp.float32)
    from repro.layers.mamba2 import _ssd_chunked

    dt = _time(jit(lambda *t: _ssd_chunked(*t, 256)[0]), xs, dts, a, bm, cm)
    chunk = 256
    flops = B * H * (S // chunk) * (
        2 * chunk * chunk * N + 2 * chunk * chunk * P + 2 * chunk * N * P * 2
    )
    byts = (xs.size + bm.size + cm.size + xs.size) * 4
    rows.append((
        "kernel_ssd_4k", dt * 1e6,
        f"gflop={flops/1e9:.2f} AI={flops/byts:.0f} "
        f"v5e_pred={max(flops/HW.peak_flops, byts/HW.hbm_bw)*1e6:.0f}us",
    ))
    return rows
