"""Kernel-sharded convolution on the TPU mesh — the paper's distribution
expressed as GSPMD shardings.

"Broadcast the inputs" = activations replicated over ``model``;
"scatter the kernels"  = HWIO weights sharded on the output-channel axis;
"gather the feature maps" = the all-gather GSPMD inserts when gather-mode
rules pin the conv output back to replicated (the sharded/megatron rules
keep feature maps channel-sharded through ReLU/LRN/pool instead — the
§Perf lever, since LRN and pooling are channel-local up to a 2-channel
halo).

On a homogeneous mesh the Eq. 1 shares degenerate to the uniform split
(its fixed point) — GSPMD shards are even by construction; the uneven
heterogeneous allocation is exercised by core/master_slave.py.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding

from repro.layers.conv import apply_conv
from repro.sharding.axes import AxisRules
from repro.sharding.partitioning import (
    constrain,
    param_sharding_for_tree,
    spec_for_shape,
)


def make_sharded_conv(rules: AxisRules):
    """conv_fn for models/cnn.py running under a mesh: the kernel axis is
    sharded over `model`, the output layout follows the rule mode."""

    def conv_fn(params, x, padding: str = "SAME"):
        y = apply_conv(params, x, padding=padding)
        # column layout right after the convolution (every mode)
        y = constrain(y, rules, "batch", None, None, "act_conv_col")
        # gather mode: force the paper's all-gather; sharded mode: keep
        y = constrain(y, rules, "batch", None, None, "act_conv")
        return y

    return conv_fn


def make_sharded_train_step(cfg, mesh, rules: AxisRules, batch: int, *,
                            lr: float = 0.05):
    """One plain-SGD training step of the paper's CNN, jitted over
    ``mesh`` with the conv kernels sharded on their output-channel axis
    per ``rules``.  Returns ``(step, (param_sh, image_sh, label_sh))``:
    call ``step(params, images, labels) -> (params, loss, acc)`` inside
    ``repro.compat.mesh_context(mesh)`` with inputs placed on those
    shardings; the new parameters keep ``param_sh``."""
    from repro.models.cnn import cnn_axes, cnn_loss, init_cnn

    conv_fn = make_sharded_conv(rules)
    abstract = jax.eval_shape(lambda: init_cnn(jax.random.key(0), cfg))
    param_sh = param_sharding_for_tree(mesh, cnn_axes(), rules, abstract)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    image_shape = (batch, cfg.image_size, cfg.image_size, cfg.image_channels)
    image_sh = NamedSharding(
        mesh, spec_for_shape(rules, image_shape, ("batch", None, None, None), sizes)
    )
    label_sh = NamedSharding(mesh, spec_for_shape(rules, (batch,), ("batch",), sizes))

    def train_step(params, images, labels):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: cnn_loss(p, images, labels, cfg=cfg, conv_fn=conv_fn),
            has_aux=True,
        )(params)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, loss, acc

    step = jax.jit(
        train_step,
        in_shardings=(param_sh, image_sh, label_sh),
        out_shardings=(param_sh, None, None),
    )
    return step, (param_sh, image_sh, label_sh)
