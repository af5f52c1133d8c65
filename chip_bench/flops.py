"""Operations and bytes of the CNN's work, counted from shapes alone.

Every convolution is stride 1, SAME, NHWC x HWIO; a multiply-add counts
two operations.  Bytes are what a kernel must read and write at least
once: its inputs and its output, at the array's item size.

    fwd: y = conv(x, w)       reads x, w;  writes y
    dX:  dx = conv^T(g, w)    reads g, w;  writes dx
    dW:  dw = corr(x, g)      reads x, g;  writes dw

Each of the three is 2*B*H*W*kh*kw*Cin*Cout operations.
"""
from __future__ import annotations

from typing import Dict, Tuple

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


def conv_layers(cfg: dict):
    """``[(name, hw, cin, cout)]`` for the two conv layers."""
    hw = cfg["image_size"]
    return [
        ("conv1", hw, cfg["image_channels"], cfg["c1_kernels"]),
        ("conv2", hw // cfg["pool_stride"], cfg["c1_kernels"], cfg["c2_kernels"]),
    ]


def model_flops_per_sample(cfg: dict) -> float:
    """Training operations one sample needs: fwd, dX and dW of both convs
    and the fc layer, without dX of the input images."""
    k = cfg["kernel_size"]
    total = 0.0
    for name, hw, cin, cout in conv_layers(cfg):
        for op in ("fwd", "dx", "dw"):
            if name == "conv1" and op == "dx":
                continue
            total += conv_work(op, 1, hw, k, cin, cout)[0]
    feat = cfg["image_size"] // cfg["pool_stride"] ** 2
    n_in = feat * feat * cfg["c2_kernels"]
    total += 3 * fc_work("fwd", 1, n_in, cfg["num_classes"])[0]
    return total


def conv_shard_work(cfg: dict, batch: int, widths: Dict[str, int], calls: int = 1,
                    with_input_dx: bool = False) -> Work:
    """The conv work of one step on a device that holds ``widths[layer]``
    output kernels of each layer and sees the whole ``batch`` in
    ``calls`` equal calls per op (microbatches).  The weight shard is
    read once per call.  ``with_input_dx`` adds dX of the first layer,
    which a runtime that differentiates its chain input computes."""
    k = cfg["kernel_size"]
    flops = nbytes = 0.0
    for name, hw, cin, _ in conv_layers(cfg):
        c = widths.get(name, 0)
        if c <= 0:
            continue
        for op in ("fwd", "dx", "dw"):
            if name == "conv1" and op == "dx" and not with_input_dx:
                continue
            f, by = conv_work(op, batch, hw, k, cin, c)
            w_bytes = k * k * cin * c * 4
            flops += f
            nbytes += by + (calls - 1) * w_bytes
    return flops, nbytes
