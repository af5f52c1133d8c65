"""Operations and bytes of the kernels' work, counted from shapes alone:
the primitives that each architecture's ``model_flops_per_sample`` and
``shard_work`` (``chip_bench/archs/<arch>.py``) add up.

Every convolution is stride 1, SAME, NHWC x HWIO; a multiply-add counts
two operations.  Bytes are what a kernel must read and write at least
once: its inputs and its output, at the array's item size.

    fwd: y = conv(x, w)       reads x, w;  writes y
    dX:  dx = conv^T(g, w)    reads g, w;  writes dx
    dW:  dw = corr(x, g)      reads x, g;  writes dw

Each of the three is 2*B*H*W*kh*kw*Cin*Cout operations.
"""
from __future__ import annotations

from typing import Tuple

Work = Tuple[float, float]  # (operations, bytes)


def conv_work(op: str, b: int, hw: int, k: int, cin: int, cout: int,
              itemsize: int = 4) -> Work:
    """``op`` is ``fwd``, ``dx`` or ``dw``."""
    flops = 2.0 * b * hw * hw * k * k * cin * cout
    x = b * hw * hw * cin
    y = b * hw * hw * cout
    w = k * k * cin * cout
    elems = {"fwd": x + w + y, "dx": y + w + x, "dw": x + y + w}[op]
    return flops, float(elems * itemsize)


def fc_work(op: str, b: int, n_in: int, n_out: int, itemsize: int = 4) -> Work:
    """A dense layer (B, n_in) x (n_in, n_out); ``op`` as for convs."""
    flops = 2.0 * b * n_in * n_out
    return flops, float((b * n_in + n_in * n_out + b * n_out) * itemsize)
