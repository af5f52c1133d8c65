"""The paper's CNN (arXiv 1712.02546, Section 5.2): its inputs, its
plain reference step and its work counts, found by the configuration
file's ``"arch": "cnn"``.

    conv(5x5, C1) + b -> ReLU -> LRN -> maxpool/2 ->
    conv(5x5, C2) + b -> ReLU -> LRN -> maxpool/2 -> fc -> softmax loss

then plain SGD on every parameter.  The parameter tree has the layout
the system under test takes (``conv1``/``conv2``/``fc``, each with
``kernel`` and ``bias``), and its values are the benchmark's own, never
the program's.  Every convolution and matrix product of the reference
runs at the precision the configuration states ("highest": fp32), so
the program and the reference differ only in summation order.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

from chip_bench.data import seed_key
from chip_bench.flops import Work, conv_work, fc_work
from chip_bench.reference import precision_of


def param_shapes(cfg: dict) -> dict:
    """Shapes of the parameter tree for a configuration file's sizes."""
    k, c1, c2 = cfg["kernel_size"], cfg["c1_kernels"], cfg["c2_kernels"]
    feat = cfg["image_size"] // cfg["pool_stride"] ** 2
    return {
        "conv1": {"kernel": (k, k, cfg["image_channels"], c1), "bias": (c1,)},
        "conv2": {"kernel": (k, k, c1, c2), "bias": (c2,)},
        "fc": {"kernel": (feat * feat * c2, cfg["num_classes"]),
               "bias": (cfg["num_classes"],)},
    }


def make_inputs(cfg: dict, batch: int, ring: int, seed: int):
    """``(params, images, labels)`` on the default device: kernels
    N(0, 1/fan_in), biases 0, ``ring`` batches of N(0, 1) images
    ``(ring, batch, H, W, C)`` and uniform labels ``(ring, batch)``."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    hw, ch, classes = cfg["image_size"], cfg["image_channels"], cfg["num_classes"]

    def gen(key):
        kp, ki, kl = jax.random.split(key, 3)
        keys = jax.random.split(kp, 3)
        params = {}
        for (name, leaf), k in zip(shapes.items(), keys):
            kshape = leaf["kernel"]
            fan_in = math.prod(kshape[:-1])
            params[name] = {
                "kernel": (jax.random.normal(k, kshape, jnp.float32)
                           / math.sqrt(fan_in)).astype(dtype),
                "bias": jnp.zeros(leaf["bias"], dtype),
            }
        images = jax.random.normal(ki, (ring, batch, hw, hw, ch), jnp.float32).astype(dtype)
        labels = jax.random.randint(kl, (ring, batch), 0, classes, jnp.int32)
        return params, images, labels

    return jax.block_until_ready(jax.jit(gen)(seed_key(seed)))


def plain_conv(x, w, precision):
    """NHWC x HWIO, stride 1, SAME."""
    from jax import lax

    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision,
    )


def lrn(x, size: int, alpha: float, beta: float, k: float):
    """Cross-channel local response normalisation:
    x / (k + alpha * sum of x^2 over the ``size`` channels around)^beta."""
    import jax.numpy as jnp

    half = size // 2
    sq = jnp.pad(jnp.square(x), [(0, 0)] * 3 + [(half, size - 1 - half)])
    c = x.shape[-1]
    window = sum(sq[..., i:i + c] for i in range(size))
    return x / jnp.power(k + alpha * window, beta)


def maxpool(x, s: int):
    """``s`` x ``s`` windows, stride ``s``, no padding."""
    import jax.numpy as jnp
    from jax import lax

    return lax.reduce_window(x, -jnp.inf, lax.max, (1, s, s, 1), (1, s, s, 1), "VALID")


def plain_dot(x, w, precision):
    import jax.numpy as jnp

    return jnp.dot(x, w, precision=precision)


def loss_fn(params, images, labels, lrn_args, pool: int, precision: str = "highest",
            conv=plain_conv, dot=plain_dot):
    """Mean softmax cross-entropy of the CNN; ``lrn_args`` is (size,
    alpha, beta, k); ``conv(x, w, precision)`` and ``dot(x, w,
    precision)`` compute the convolutions and the fc product (replaced
    only by the control and the planted faults)."""
    import jax
    import jax.numpy as jnp

    prec = precision_of(precision)
    x = images
    for layer in ("conv1", "conv2"):
        x = conv(x, params[layer]["kernel"], prec) + params[layer]["bias"]
        x = maxpool(lrn(jax.nn.relu(x), *lrn_args), pool)
    x = x.reshape(x.shape[0], -1)
    logits = dot(x, params["fc"]["kernel"], prec) + params["fc"]["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.lru_cache(maxsize=16)
def _jitted_step(lrn_args, pool: int, lr: float, precision: str, conv, dot):
    import jax

    def step(params, images, labels):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, images, labels, lrn_args, pool, precision, conv, dot)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss

    return jax.jit(step)


def reference_step(cfg: dict, lr: float, precision: str = "highest", conv=plain_conv,
                   dot=plain_dot):
    """``step(params, images, labels) -> (new_params, loss)``, jitted."""
    n = cfg["lrn"]
    lrn_args = (int(n["size"]), float(n["alpha"]), float(n["beta"]), float(n["k"]))
    return _jitted_step(lrn_args, int(cfg["pool_stride"]), float(lr), precision, conv, dot)


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


def shard_work(cfg: dict, batch: int, widths: Dict[str, int], calls: int = 1,
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
