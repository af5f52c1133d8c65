"""Pluggable conv compute backends for the distributed engine.

The paper distributes ONE operation — the stride-1 SAME convolution over
the output-channel ("kernel") axis — so every device in the cluster only
ever needs two primitives:

    conv(x, w)        -> y                (Algorithm 2's `convn`)
    conv_vjp(x, w, g) -> (dx, dw)         (the backward shard)

``ConvBackend`` pins that contract; the registry maps a name to an
implementation so a heterogeneous cluster can mix devices running
different kernels (the paper's CPU/GPU scenario):

    numpy   — serial im2col, callback- and thread-safe everywhere; the
              master's default since it runs inside jax host callbacks
              where re-entering jit dispatch can deadlock the runtime.
    xla     — ``jax.lax.conv_general_dilated`` jitted per shape (jit's
              own cache keys on shapes/dtypes).
    pallas  — the MXU direct-conv kernel (kernels/conv2d.py) forward and
              the Pallas dX/dW backward, compiled for the TPU; off a TPU
              it raises unless interpret mode is asked for by name
              (``"pallas:interpret"``).

Every primitive takes numpy or ``jax.Array`` inputs and returns its
result on the side it computes on: ``numpy`` on the host (a device
input comes down through ``repro.tracing.to_host``), ``xla`` and
``pallas`` on JAX's default device (only numpy inputs go up, through
``to_device``), waited for under ``device.wait`` so that a caller's
clock holds the compute.  The cluster keeps results on the chip
between its stages (``core/cluster/sides.py``).  ``probe_conv_time``
times the SAME code a device will run for the real workload, so the
Eq. 1 shares computed from probe times are exact per backend.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Tuple

import numpy as np

from repro.tracing import ready, to_device, to_host


class ConvBackend:
    """The per-device compute contract of the distributed conv engine."""

    name: str = "base"
    # computes on the host from numpy: a slave caches its kernels there
    host: bool = False

    def conv(self, x, w):
        """NHWC x HWIO -> NHWC, SAME padding, stride 1; numpy or device
        inputs, the result on the side the backend computes on."""
        raise NotImplementedError

    def conv_vjp(self, x, w, g):
        """(dx, dw) of sum(conv(x, w) * g), on the side ``conv``'s is."""
        raise NotImplementedError


_REGISTRY: Dict[str, Callable[..., ConvBackend]] = {}
_INSTANCES: Dict[str, ConvBackend] = {}


def register_backend(name: str):
    """Class decorator: ``@register_backend("mine")`` adds a factory."""

    def deco(factory: Callable[..., ConvBackend]):
        _REGISTRY[name] = factory
        return factory

    return deco


def get_backend(name: str) -> ConvBackend:
    """Resolve (and cache) a backend instance by registry name.

    Names may carry a parameter after a colon — ``"sim:5e9"`` is a sim
    device at 5 GFLOP/s, ``"pallas:interpret"`` forces interpret mode —
    so one cluster can mix several instances of the same backend at
    different speeds without the per-device ``slowdown`` workaround.
    Each parameterized name caches its OWN instance."""
    if name not in _INSTANCES:
        base, _, param = name.partition(":")
        if base not in _REGISTRY:
            raise KeyError(
                f"unknown conv backend {name!r}; available: {available_backends()}"
            )
        try:
            _INSTANCES[name] = _REGISTRY[base](param) if param else _REGISTRY[base]()
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"backend {base!r} rejected parameter {param!r}: {e}"
            ) from e
    return _INSTANCES[name]


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# numpy: serial im2col — the seed implementation, kept as the reference
# and as the only backend safe inside jax host callbacks.
# ---------------------------------------------------------------------------


def _conv_windows(x: np.ndarray, kh: int, kw: int) -> np.ndarray:
    """SAME-padded sliding windows as a zero-copy strided VIEW.
    x: (B,H,W,C) -> view (B,H,W,C,kh,kw)."""
    ph, pw = kh // 2, kw // 2
    xp = np.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))
    return np.lib.stride_tricks.sliding_window_view(xp, (kh, kw), axis=(1, 2))


def _im2col(x: np.ndarray, kh: int, kw: int) -> np.ndarray:
    """SAME-padded im2col.  x: (B,H,W,C) -> (B,H,W, kh*kw*C).

    Materializes a contiguous copy of the windows — kept ONLY where the
    reshape-to-matrix genuinely requires it: for kh,kw > 1 the single
    large BLAS GEMM it enables beats every measured copy-free
    formulation (tensordot/einsum on the strided view re-materialize the
    same copy internally; per-tap shifted GEMMs lose to the strided
    accumulate), and the VJP's ``cols.T @ g`` has no matrix without it.
    The 1x1 forward skips the lowering entirely (see ``numpy_conv``)."""
    b, h, w, c = x.shape
    win = _conv_windows(x, kh, kw).transpose(0, 1, 2, 4, 5, 3)
    return np.ascontiguousarray(win).reshape(b, h, w, kh * kw * c)


def numpy_conv(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """NHWC x HWIO SAME conv, stride 1 (the slave's `convn`).

    1x1 kernels take the lowering-free hot path: one GEMM on a FREE
    reshape of the contiguous input — no pad, no window copy (1.4-17x
    measured, ``numpy_fwd_1x1_nocopy`` in bench_kernels).  Larger
    kernels keep the im2col copy the GEMM genuinely needs (see
    ``_im2col``)."""
    kh, kw, cin, cout = w.shape
    x = np.asarray(x, np.float32)
    if kh == 1 and kw == 1:
        b, h, wd, _ = x.shape
        return (x.reshape(-1, cin) @ w[0, 0]).reshape(b, h, wd, cout)
    cols = _im2col(x, kh, kw)
    y = cols.reshape(-1, kh * kw * cin) @ w.reshape(kh * kw * cin, cout)
    return y.reshape(x.shape[0], x.shape[1], x.shape[2], cout)


def numpy_conv_vjp(x: np.ndarray, w: np.ndarray, g: np.ndarray):
    """Returns (dx, dw) of sum(conv(x, w) * g)."""
    x = np.asarray(x, np.float32)
    g = np.asarray(g, np.float32)
    kh, kw, cin, cout = w.shape
    if cout == 0:  # legal: a device allocated 0 kernels contributes nothing
        return np.zeros(x.shape, np.float32), np.zeros(w.shape, np.float32)
    b, h, wd, _ = x.shape
    cols = _im2col(x, kh, kw).reshape(-1, kh * kw * cin)
    dw = (cols.T @ g.reshape(-1, cout)).reshape(kh, kw, cin, cout)
    # dx: scatter the columns of dG @ W^T back into the padded image
    dcols = (g.reshape(-1, cout) @ w.reshape(kh * kw * cin, cout).T).reshape(
        b, h, wd, kh, kw, cin
    )
    ph, pw = kh // 2, kw // 2
    dxp = np.zeros((b, h + kh - 1, wd + kw - 1, cin), np.float32)
    for di in range(kh):
        for dj in range(kw):
            dxp[:, di : di + h, dj : dj + wd, :] += dcols[:, :, :, di, dj, :]
    dx = dxp[:, ph : ph + h, pw : pw + wd, :]
    return dx, dw


@register_backend("numpy")
class NumpyBackend(ConvBackend):
    name = "numpy"
    host = True

    def conv(self, x, w):
        return numpy_conv(to_host(x), to_host(w))

    def conv_vjp(self, x, w, g):
        return numpy_conv_vjp(to_host(x), to_host(w), to_host(g))


# ---------------------------------------------------------------------------
# height-strip (spatial) partitioning helpers — shared by the master and
# every slave, on top of ANY backend's plain SAME conv primitives.
# ---------------------------------------------------------------------------


def strip_conv(
    backend: ConvBackend,
    x_halo: np.ndarray,
    w: np.ndarray,
    pad_top: int,
    pad_bot: int,
) -> np.ndarray:
    """Forward of one height strip of a SAME stride-1 conv.

    ``x_halo`` holds the strip's input rows plus the ``kh//2`` halo rows
    on each side, CLIPPED at the image border; ``pad_top``/``pad_bot``
    zero-rows restore what the clip removed, so the padded strip carries
    exactly the receptive field of the strip's output rows (the zeros
    coincide with the global SAME padding).  Runs the backend's ordinary
    SAME conv on the padded strip and slices out the interior rows —
    every backend works unchanged.  Assumes odd ``kh`` (the repo's
    ``kh//2``-low padding convention; even kernels differ per backend).
    Returns the strip's output rows: (B, strip_h, W, cout), on the host:
    the strip arithmetic pads and slices in numpy, so a device input
    comes down through ``to_host`` and the backend's result comes back
    the same way."""
    kh = w.shape[0]
    ph = kh // 2
    strip_h = x_halo.shape[1] + pad_top + pad_bot - (kh - 1)
    if strip_h <= 0:  # a device legally allocated 0 rows
        return np.zeros(
            (x_halo.shape[0], 0, x_halo.shape[2], w.shape[-1]), np.float32
        )
    xp = np.pad(to_host(x_halo), ((0, 0), (pad_top, pad_bot), (0, 0), (0, 0)))
    y = to_host(backend.conv(xp, w), np.float32)
    return y[:, ph : ph + strip_h]


def strip_conv_vjp(
    backend: ConvBackend,
    x_halo: np.ndarray,
    w: np.ndarray,
    g_strip: np.ndarray,
    pad_top: int,
    pad_bot: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Backward of one height strip: ``(dx_halo, dw_partial)``.

    ``dx_halo`` covers the strip PLUS its halo rows — contributions of
    this strip's output-gradient rows to neighbouring strips' inputs —
    so the master must overlap-ADD the seams when reassembling the full
    dX.  ``dw_partial`` is this strip's contribution to the FULL kernel
    gradient (strips see every output channel); the master sums it.
    Both on the host, like ``strip_conv``'s result."""
    kh = w.shape[0]
    ph = kh // 2
    strip_h = g_strip.shape[1]
    if strip_h == 0 or x_halo.shape[1] == 0:
        return (
            np.zeros(x_halo.shape, np.float32),
            np.zeros(w.shape, np.float32),
        )
    xp = np.pad(to_host(x_halo), ((0, 0), (pad_top, pad_bot), (0, 0), (0, 0)))
    gp = np.zeros(xp.shape[:-1] + (w.shape[-1],), np.float32)
    gp[:, ph : ph + strip_h] = to_host(g_strip)
    dxp, dw = backend.conv_vjp(xp, w, gp)
    dxp = to_host(dxp, np.float32)
    return dxp[:, pad_top : pad_top + x_halo.shape[1]], to_host(dw, np.float32)


# ---------------------------------------------------------------------------
# xla: jax.lax.conv_general_dilated, jitted per shape.
# ---------------------------------------------------------------------------


@register_backend("xla")
class XlaBackend(ConvBackend):
    name = "xla"

    def __init__(self):
        import jax

        def _conv(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
            )

        def _vjp(x, w, g):
            _, pullback = jax.vjp(_conv, x, w)
            return pullback(g)

        # jit caches per (shape, dtype), so every shard shape compiles once
        self._conv = jax.jit(_conv)
        self._vjp = jax.jit(_vjp)

    # numpy inputs are put on JAX's default device explicitly, as the
    # jit call would put them, so that the upload is timed and counted
    def conv(self, x, w):
        return ready(self._conv(to_device(x), to_device(w)))

    def conv_vjp(self, x, w, g):
        return ready(self._vjp(to_device(x), to_device(w), to_device(g)))


# ---------------------------------------------------------------------------
# pallas: the MXU direct-conv kernel + the Pallas dX/dW backward.
# ---------------------------------------------------------------------------


def require_tpu(what: str) -> None:
    """Raise unless JAX's default device is a TPU: compiled Pallas kernels
    run nowhere else, and silently interpreting them instead would hide a
    lost chip behind a run orders of magnitude slower."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"{what} runs Pallas kernels compiled for a TPU, but JAX's "
            f"default device is {platform!r}; ask for interpret mode by "
            f"name ('pallas:interpret', or interpret=True) to run them "
            f"slowly on this platform"
        )


def backend_platform(name: str) -> str:
    """The platform backend ``name`` computes on in this process: ``cpu``
    for the host-only numpy and sim backends (no jax import), else JAX's
    default device platform."""
    if name.partition(":")[0] in ("numpy", "sim"):
        return "cpu"
    import jax

    return jax.devices()[0].platform


@register_backend("pallas")
class PallasBackend(ConvBackend):
    """Runs kernels/conv2d.py compiled for the TPU.  ``"pallas:interpret"``
    (``interpret=True``) runs the same kernels in Pallas interpret mode
    on any platform — bit-accurate but slow, meant for CPU parity tests;
    plain ``"pallas"`` off a TPU raises instead."""

    name = "pallas"

    def __init__(self, interpret=False):
        if isinstance(interpret, str):  # registry parameter, e.g. "pallas:interpret"
            if interpret not in ("interpret", "compiled"):
                raise ValueError(
                    f"pallas parameter must be 'interpret' or 'compiled', got {interpret!r}"
                )
            interpret = interpret == "interpret"
        self.interpret = bool(interpret)
        if not self.interpret:
            require_tpu("the 'pallas' backend")

    def conv(self, x, w):
        from repro.kernels.conv2d import conv2d_pallas

        return ready(
            conv2d_pallas(to_device(x), to_device(w), interpret=self.interpret)
        )

    def conv_vjp(self, x, w, g):
        from repro.kernels.conv2d import conv2d_dw_pallas, conv2d_dx_pallas

        kh, kw = w.shape[0], w.shape[1]
        g = to_device(g)
        dx = conv2d_dx_pallas(g, to_device(w), interpret=self.interpret)
        dw = conv2d_dw_pallas(to_device(x), g, kh, kw, interpret=self.interpret)
        return ready((dx, dw))


# ---------------------------------------------------------------------------
# sim: a deterministic virtual device for protocol/scheduling studies.
# ---------------------------------------------------------------------------


@register_backend("sim")
class SimBackend(ConvBackend):
    """Sleeps exactly ``flops / flops_per_s`` and returns ZEROS of the
    right shape.  Wall-clock behaves like a device of known speed with
    none of the host's compute noise — for benchmarking the master/slave
    protocol schedule (bench_master_slave.py), NEVER for numerics.  It
    reads only its inputs' shapes, so a device input never crosses."""

    name = "sim"

    def __init__(self, flops_per_s=1e9):
        # accepts the registry parameter string: "sim:5e9" = 5 GFLOP/s
        self.flops_per_s = float(flops_per_s)
        if self.flops_per_s <= 0:
            raise ValueError("sim flops_per_s must be positive")

    def _flops(self, x, w) -> float:
        b, h, wd, _ = x.shape
        kh, kw, cin, cout = w.shape
        return 2.0 * b * h * wd * kh * kw * cin * cout

    def conv(self, x, w):
        time.sleep(self._flops(x, w) / self.flops_per_s)
        return np.zeros(x.shape[:-1] + (w.shape[-1],), np.float32)

    def conv_vjp(self, x, w, g):
        # backward is ~2x the forward cost (dX + dW)
        time.sleep(2.0 * self._flops(x, w) / self.flops_per_s)
        return np.zeros(x.shape, np.float32), np.zeros(w.shape, np.float32)


# ---------------------------------------------------------------------------
# probing — §4.1.1, generalized so each device times its OWN backend.
# ---------------------------------------------------------------------------


def probe_conv_time(
    backend,
    *,
    image_size: int,
    in_channels: int,
    kernel_size: int,
    num_kernels: int,
    batch: int,
    repeats: int = 3,
    slowdown: float = 1.0,
    seed: int = 0,
) -> float:
    """The paper's probe: median wall-clock of the reference convolution
    on the given backend (name or instance), scaled by the emulated
    slowdown — in BOTH directions: ``slowdown < 1.0`` emulates a FASTER
    device and must scale too, or its Eq. 1 share would be computed from
    the unscaled host time.  (HeteroCluster rejects sub-1 slowdowns —
    its op-level emulation can only sleep — but standalone Eq. 1 inputs
    for genuinely faster remote devices need the scaling, as do
    parameterized sim backends.)  Probing the backend a device actually
    runs keeps the Eq. 1 ratios exact for mixed-backend clusters."""
    if slowdown <= 0:
        raise ValueError(f"slowdown must be positive, got {slowdown}")
    if isinstance(backend, str):
        backend = get_backend(backend)
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, image_size, image_size, in_channels)).astype(np.float32)
    w = rng.normal(
        size=(kernel_size, kernel_size, in_channels, num_kernels)
    ).astype(np.float32)
    backend.conv(x, w)  # warm caches / jit
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        backend.conv(x, w)
        times.append(time.perf_counter() - t0)
    measured = float(np.median(times))
    return measured * slowdown


# ---------------------------------------------------------------------------
# jax-level conv_fn factory — threads a backend choice into models/cnn.py
# (single-process path; the cluster path lives in core/master_slave.py).
# ---------------------------------------------------------------------------


def make_conv_fn(name: str, *, interpret: bool = False):
    """Return a ``conv_fn(params, x)`` for ``cnn_forward`` that computes
    the convolution with the named backend, differentiable end to end.
    ``pallas`` needs a TPU unless ``interpret=True`` (or the registry
    spelling ``"pallas:interpret"``)."""
    import jax

    if name == "pallas:interpret":
        name, interpret = "pallas", True
    if name == "xla":
        from repro.layers.conv import apply_conv

        return apply_conv

    if name == "pallas":
        from repro.kernels.conv2d import (
            conv2d_dw_pallas,
            conv2d_dx_pallas,
            conv2d_pallas,
        )

        interp = bool(interpret)
        if not interp:
            require_tpu("make_conv_fn('pallas')")

        @jax.custom_vjp
        def pconv(x, w):
            return conv2d_pallas(x, w, interpret=interp)

        def pconv_fwd(x, w):
            return pconv(x, w), (x, w)

        def pconv_bwd(res, g):
            x, w = res
            dx = conv2d_dx_pallas(g, w, interpret=interp)
            dw = conv2d_dw_pallas(x, g, w.shape[0], w.shape[1], interpret=interp)
            return dx, dw.astype(w.dtype)

        pconv.defvjp(pconv_fwd, pconv_bwd)

        def conv_fn(params, x, padding: str = "SAME"):
            y = pconv(x, params["kernel"].astype(x.dtype))
            return y + params["bias"].astype(y.dtype)[None, None, None, :]

        return conv_fn

    if name == "numpy":
        backend = get_backend("numpy")

        @jax.custom_vjp
        def nconv(x, w):
            return _np_callback_conv(x, w)

        def _np_callback_conv(x, w):
            out_shape = jax.ShapeDtypeStruct(x.shape[:-1] + (w.shape[-1],), x.dtype)
            return jax.pure_callback(
                lambda xx, ww: backend.conv(np.asarray(xx), np.asarray(ww)).astype(
                    xx.dtype
                ),
                out_shape, x, w,
            )

        def nconv_fwd(x, w):
            return _np_callback_conv(x, w), (x, w)

        def nconv_bwd(res, g):
            x, w = res
            out_shape = (
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(w.shape, w.dtype),
            )
            return jax.pure_callback(
                lambda xx, ww, gg: tuple(
                    np.asarray(o, xx.dtype)
                    for o in backend.conv_vjp(
                        np.asarray(xx), np.asarray(ww), np.asarray(gg)
                    )
                ),
                out_shape, x, w, g,
            )

        nconv.defvjp(nconv_fwd, nconv_bwd)

        def conv_fn(params, x, padding: str = "SAME"):
            y = nconv(x, params["kernel"].astype(x.dtype))
            return y + params["bias"].astype(y.dtype)[None, None, None, :]

        return conv_fn

    raise KeyError(f"no conv_fn for backend {name!r}; available: {available_backends()}")
