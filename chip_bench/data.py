"""The one generator of weights and batches: everything a run trains on
comes from ``--seed`` through ``make_inputs``, in one jitted call on the
default device.

A training job here is closed-loop: step ``i`` takes batch ``i % ring``
of a ring of ``ring`` distinct batches.  The parameter tree has the
layout the system under test takes (``conv1``/``conv2`` with ``kernel``
and ``bias``, ``fc`` with ``kernel`` and ``bias``), and its values are
the benchmark's own, never the program's.
"""
from __future__ import annotations

import math


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a seed of up to 64 bits
    (``jax.random.key`` keeps only the low 32 without x64)."""
    import jax

    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


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
