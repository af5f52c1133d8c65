"""Pallas TPU direct-convolution kernel — the paper's compute hot-spot,
adapted to the MXU.

The GPU papers of the era (Ward et al. [11]) tile the *image*; on TPU the
natural tiling is the one the paper itself distributes across devices:
the OUTPUT-CHANNEL axis.  Each grid step owns one batch image, one
128-wide slice of output channels (MXU lane width) and one block of the
contracted input channels; it unrolls the kh x kw taps and issues
(H*W, cin_blk) x (cin_blk, 128) matmuls whose fp32 sum accumulates in a
VMEM scratch across the innermost (contracted) grid axis — the kernel is
the single-device microcosm of the distribution scheme (output channels
= kernels are the parallel axis at every level).

VMEM per step is bounded by the tiles, not by the layer's widths:
  x block (1, H+kh-1, W+kw-1, 256) + w block (kh, kw, 256, 128)
  + acc (H*W, 128), each input double-buffered — ~7 MB for the paper's
  widest layer (5x5, 16x16, Cin=500/Cout=1500), under the scoped VMEM
  limit.  Loading the whole contracted axis in one block instead
  (5x5x1500x128 fp32 = 19 MB for conv2's dX) is refused by the TPU
  compiler.  A contracted axis wider than one block is zero-padded to a
  whole number of blocks (zeros add nothing to the sum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _channel_tiles(c: int, tile: int):
    """``(tile_width, pad)`` for a channel axis of ``c``: the whole axis
    while it fits one tile, else ``tile``-wide tiles after padding ``c``
    up by ``pad`` zeros."""
    if c <= tile:
        return c, 0
    return tile, (-c) % tile


def _conv2d_kernel(x_ref, w_ref, o_ref, acc_ref, *, kh: int, kw: int,
                   out_h: int, out_w: int):
    """x_ref: (1, out_h+kh-1, out_w+kw-1, tci) padded input block (VMEM)
    w_ref: (kh, kw, tci, tco); o_ref: (1, out_h, out_w, tco);
    acc_ref: (out_h*out_w, tco) fp32, summed over the contracted grid
    axis (innermost, so the output block stays resident)."""
    ci = pl.program_id(2)
    tci = x_ref.shape[-1]
    tco = o_ref.shape[-1]

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    acc = jnp.zeros((out_h * out_w, tco), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            # (out_h, out_w, tci) shifted window, flattened to an MXU matmul
            xs = x_ref[0, i : i + out_h, j : j + out_w, :].reshape(
                out_h * out_w, tci
            )
            ws = w_ref[i, j, :, :]  # (tci, tco)
            acc += jnp.dot(
                xs.astype(jnp.float32),
                ws.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
    acc_ref[...] += acc

    @pl.when(ci == pl.num_programs(2) - 1)
    def _store():
        o_ref[0] = acc_ref[...].reshape(out_h, out_w, tco).astype(o_ref.dtype)


def _direct_conv(xp: jax.Array, w: jax.Array, out_h: int, out_w: int,
                 out_tile: int, contract_tile: int, interpret: bool) -> jax.Array:
    """Shared driver: pre-padded input xp (B, out_h+kh-1, out_w+kw-1, Cin)
    against w (kh, kw, Cin, Cout), tiled over batch x Cout x Cin (the
    contracted axis innermost)."""
    b = xp.shape[0]
    kh, kw, cin, cout = w.shape

    tco, pad_co = _channel_tiles(cout, out_tile)
    tci, pad_ci = _channel_tiles(cin, contract_tile)
    if pad_co or pad_ci:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, pad_ci), (0, pad_co)))
    if pad_ci:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, 0), (0, pad_ci)))
    n_co = w.shape[-1] // tco
    n_ci = w.shape[2] // tci

    out = pl.pallas_call(
        functools.partial(_conv2d_kernel, kh=kh, kw=kw, out_h=out_h, out_w=out_w),
        grid=(b, n_co, n_ci),
        in_specs=[
            pl.BlockSpec(
                (1, out_h + kh - 1, out_w + kw - 1, tci),
                lambda bi, co, ci: (bi, 0, 0, ci),
            ),
            pl.BlockSpec((kh, kw, tci, tco), lambda bi, co, ci: (0, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec(
            (1, out_h, out_w, tco), lambda bi, co, ci: (bi, 0, 0, co)
        ),
        out_shape=jax.ShapeDtypeStruct((b, out_h, out_w, w.shape[-1]), xp.dtype),
        scratch_shapes=[pltpu.VMEM((out_h * out_w, tco), jnp.float32)],
        interpret=interpret,
    )(xp, w)
    if pad_co:
        out = out[..., :cout]
    return out


@functools.partial(
    jax.jit, static_argnames=("interpret", "cout_tile", "contract_tile")
)
def conv2d_pallas(
    x: jax.Array,  # (B, H, W, Cin)
    w: jax.Array,  # (kh, kw, Cin, Cout)
    *,
    cout_tile: int = 128,
    contract_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """SAME-padded stride-1 convolution.  Cout is padded to the tile."""
    b, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))
    return _direct_conv(xp, w, h, wd, cout_tile, contract_tile, interpret)


@functools.partial(
    jax.jit, static_argnames=("interpret", "cin_tile", "contract_tile")
)
def conv2d_dx_pallas(
    g: jax.Array,  # (B, H, W, Cout) — upstream gradient
    w: jax.Array,  # (kh, kw, Cin, Cout) — the forward kernel (shard)
    *,
    cin_tile: int = 128,
    contract_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """dX of the SAME stride-1 conv: the transpose convolution, expressed
    as a direct conv of g against the spatially flipped, channel-swapped
    kernel — so it reuses the exact forward MXU kernel with Cin as the
    tiled output axis and Cout as the contracted one.  The pad is the
    complement of the forward pad (identical for odd kernels)."""
    kh, kw = w.shape[0], w.shape[1]
    ph, pw = kh // 2, kw // 2
    wt = jnp.flip(w, axis=(0, 1)).transpose(0, 1, 3, 2)  # (kh, kw, Cout, Cin)
    gp = jnp.pad(g, ((0, 0), (kh - 1 - ph, ph), (kw - 1 - pw, pw), (0, 0)))
    return _direct_conv(
        gp, wt, g.shape[1], g.shape[2], cin_tile, contract_tile, interpret
    )


def _conv2d_dw_kernel(x_ref, g_ref, o_ref, *, kh: int, kw: int, out_h: int, out_w: int):
    """x_ref: (1, out_h+kh-1, out_w+kw-1, tci) padded input block (VMEM)
    g_ref: (1, out_h, out_w, tco); o_ref: (kh, kw, tci, tco), accumulated
    over the batch grid axis (innermost, so writes are consecutive)."""
    tci = x_ref.shape[-1]
    tco = g_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    gs = g_ref[0].reshape(out_h * out_w, tco).astype(jnp.float32)
    for i in range(kh):
        for j in range(kw):
            xs = x_ref[0, i : i + out_h, j : j + out_w, :].reshape(
                out_h * out_w, tci
            ).astype(jnp.float32)
            # contract the pixel axis: (tci, tco) += xs^T @ gs on the MXU
            o_ref[i, j] += jax.lax.dot_general(
                xs, gs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("kh", "kw", "interpret", "cout_tile", "cin_tile")
)
def conv2d_dw_pallas(
    x: jax.Array,  # (B, H, W, Cin)
    g: jax.Array,  # (B, H, W, Cout)
    kh: int,
    kw: int,
    *,
    cout_tile: int = 128,
    cin_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """dW of the SAME stride-1 conv: per-tap (Cin, Cout) matmuls between
    shifted input windows and the upstream gradient, accumulated across
    the batch in fp32 (batch is the innermost grid axis so each
    (Cin, Cout) tile of dW is revisited consecutively; tiling Cin too
    keeps the resident dW block bounded for wide layers)."""
    b, h, wd, cin = x.shape
    cout = g.shape[-1]
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))

    tco, pad_co = _channel_tiles(cout, cout_tile)
    tci, pad_ci = _channel_tiles(cin, cin_tile)
    if pad_co:
        g = jnp.pad(g, ((0, 0), (0, 0), (0, 0), (0, pad_co)))
    if pad_ci:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, 0), (0, pad_ci)))
    n_co = g.shape[-1] // tco
    n_ci = xp.shape[-1] // tci

    out = pl.pallas_call(
        functools.partial(_conv2d_dw_kernel, kh=kh, kw=kw, out_h=h, out_w=wd),
        grid=(n_co, n_ci, b),
        in_specs=[
            pl.BlockSpec(
                (1, h + kh - 1, wd + kw - 1, tci),
                lambda co, ci, bi: (bi, 0, 0, ci),
            ),
            pl.BlockSpec((1, h, wd, tco), lambda co, ci, bi: (bi, 0, 0, co)),
        ],
        out_specs=pl.BlockSpec((kh, kw, tci, tco), lambda co, ci, bi: (0, 0, ci, co)),
        out_shape=jax.ShapeDtypeStruct(
            (kh, kw, xp.shape[-1], g.shape[-1]), jnp.float32
        ),
        interpret=interpret,
    )(xp, g)
    return out[:, :, :cin, :cout]
