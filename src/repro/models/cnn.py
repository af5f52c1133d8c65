"""The paper's CIFAR-10 CNN (§5.2):

    conv(5x5, C1) -> LRN -> maxpool/2 -> conv(5x5, C2) -> LRN ->
    maxpool/2 -> fully-connected -> softmax loss

Four sizes are studied: (C1, C2) in {(50,500), (150,800), (300,1000),
(500,1500)}.  The conv output-channel axis is the paper's distribution
axis; ``core/conv_shard.py`` shards it over the mesh and
``core/master_slave.py`` runs it over the emulated socket cluster —
which can alternatively split the HEIGHT axis (spatial strips + halo
exchange) or pick the cheaper axis per layer (``partition="auto"``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import CNNConfig
from repro.layers.conv import apply_conv, conv_axes, init_conv, max_pool
from repro.layers.linear import apply_dense, dense_axes, init_dense
from repro.layers.norm import local_response_norm
from repro.tracing import span, to_device, to_host


PAPER_SIZES = {
    "cifar_cnn_50_500": (50, 500),
    "cifar_cnn_150_800": (150, 800),
    "cifar_cnn_300_1000": (300, 1000),
    "cifar_cnn_500_1500": (500, 1500),
}


def make_cnn_config(c1: int, c2: int) -> CNNConfig:
    return CNNConfig(arch_id=f"cifar_cnn_{c1}_{c2}", c1_kernels=c1, c2_kernels=c2)


def init_cnn(key, cfg: CNNConfig):
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    k = cfg.kernel_size
    feat = cfg.image_size // (cfg.pool_stride ** 2)
    return {
        "conv1": init_conv(ks[0], k, k, cfg.image_channels, cfg.c1_kernels, dtype),
        "conv2": init_conv(ks[1], k, k, cfg.c1_kernels, cfg.c2_kernels, dtype),
        "fc": init_dense(
            ks[2], (feat * feat * cfg.c2_kernels,), (cfg.num_classes,), dtype, use_bias=True
        ),
    }


def cnn_axes():
    return {
        "conv1": conv_axes(),
        "conv2": conv_axes(),
        "fc": dense_axes((None,), (None,), use_bias=True),
    }


def conv_fn_for_backend(backend: str = "xla", *, interpret: bool = False):
    """Return a ``conv_fn`` for ``cnn_forward`` that computes the
    convolutions with the named compute backend (core/backends.py):
    ``xla`` (lax conv, the default reference), ``pallas`` (the MXU
    kernels forward + Pallas dX/dW backward; a TPU, or
    ``interpret=True``), or ``numpy`` (im2col via
    host callback).  The distributed variants stay separate:
    core/conv_shard.py (mesh) and core/master_slave.py (cluster)."""
    from repro.core.backends import make_conv_fn

    return make_conv_fn(backend, interpret=interpret)


def cnn_forward(params, images: jax.Array, *, cfg: CNNConfig,
                conv_fn=apply_conv) -> jax.Array:
    """images: (B, 32, 32, 3) NHWC -> logits (B, 10).

    ``conv_fn`` is injectable so the distributed variants
    (core/conv_shard.py, core/master_slave.py) and the Pallas kernel can
    replace only the convolution, exactly as the paper replaces only the
    convolution step.
    """
    x = conv_fn(params["conv1"], images)
    x = jax.nn.relu(x)
    x = local_response_norm(x)
    x = max_pool(x, cfg.pool_stride, cfg.pool_stride)
    x = conv_fn(params["conv2"], x)
    x = jax.nn.relu(x)
    x = local_response_norm(x)
    x = max_pool(x, cfg.pool_stride, cfg.pool_stride)
    x = x.reshape(x.shape[0], -1)
    return apply_dense(params["fc"], x)


def cnn_loss(params, images: jax.Array, labels: jax.Array, *, cfg: CNNConfig,
             conv_fn=apply_conv) -> Tuple[jax.Array, jax.Array]:
    logits = cnn_forward(params, images, cfg=cfg, conv_fn=conv_fn)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, acc


def make_cluster_train_step(cluster, cfg: CNNConfig, *, lr: float = 0.05):
    """Full training steps of the paper's CNN over a HeteroCluster via the
    pipelined ``conv_train_step`` schedule: both conv layers run
    distributed — forward and backward — while the master-only stages
    (bias add, ReLU, LRN, pool, fc, softmax loss) overlap slave compute
    through the activation-stashing pipeline.

    This is a DIRECT driver (no jax host callbacks), so unlike
    ``make_distributed_conv`` it is safe with any master backend, and the
    cluster's comp-aware partitioner sees the master's real non-conv duty.

    The step is device-resident: images and labels go up once, and the
    activations, gradients, kernels and every SGD update stay on JAX's
    default device.  Members on the chip (``xla``, ``pallas``) compute
    there and the master assembles their shards there; only a host
    member's (``numpy``) inputs come down and its outputs go up, and the
    loss and accuracy come down once, at the step's end.

    The cluster's partition axis is transparent here: with
    ``partition="spatial"`` (or ``"auto"``) the chain ships height strips
    + halos instead of full activations and seam-sums the dX halos on the
    master, and with ``wire_dtype="fp16"/"bf16"`` activations/gradients
    cross the wire in 2 bytes — the step's numerics stay float32 on the
    master either way (the codec narrows only the wire).

    Returns ``step(params, images, labels) -> (new_params, loss, acc)``
    applying plain SGD with ``lr`` to every parameter.
    """

    def _stage(y, b):
        """The master-only block after each conv: +bias, ReLU, LRN, pool."""
        z = jax.nn.relu(y + b[None, None, None, :])
        z = local_response_norm(z)
        return max_pool(z, cfg.pool_stride, cfg.pool_stride)

    def _head_sums(z, fc, labels, denom):
        """Loss contribution (sum/denom) + correct-count of one microbatch."""
        logits = apply_dense(fc, z.reshape(z.shape[0], -1))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1)) / denom
        correct = jnp.sum((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return loss, correct

    # jit the master-only stages (cached per microbatch shape); the
    # backward halves rematerialize the forward instead of holding jax
    # residuals across the pipeline.  Python numbers enter the jits as
    # compile-time constants, never as arguments to copy up.
    _stage_fwd = jax.jit(_stage)
    _stage_bwd = jax.jit(lambda y, b, gz: jax.vjp(_stage, y, b)[1](gz))

    @functools.partial(jax.jit, static_argnums=3)
    def _head_both(z, fc, labels, denom):
        (loss, correct), vjp = jax.vjp(
            lambda zz, f: _head_sums(zz, f, labels, denom), z, fc
        )
        gz, gfc = vjp((jnp.ones((), jnp.float32), jnp.zeros((), jnp.float32)))
        return loss, correct, gz, gfc

    @jax.jit
    def _sgd(params, grads):
        """Plain SGD at fp32 on the device, over any pytree."""
        return jax.tree.map(lambda p, g: p - lr * g, params, grads)

    # each microbatch's (loss, correct), stacked to come down in one copy
    _stack = jax.jit(lambda head_aux: jnp.asarray(head_aux, jnp.float32))

    warmed: set = set()  # (microbatch, batch) sizes whose jits are compiled

    def _warm(mb, batch, params):
        """Compile the master-only jits for this microbatch size OUTSIDE
        the pipeline: one-time compilation must not pollute the cluster's
        measured non-conv duty (it would strip the master's conv share)."""
        if (mb, batch) in warmed:
            return
        warmed.add((mb, batch))
        h1 = cfg.image_size
        h2, h3 = h1 // cfg.pool_stride, h1 // cfg.pool_stride ** 2
        for h, c, b in ((h1, cfg.c1_kernels, params["conv1"]["bias"]),
                        (h2, cfg.c2_kernels, params["conv2"]["bias"])):
            y = jnp.zeros((mb, h, h, c), jnp.float32)
            gz = jnp.zeros((mb, h // cfg.pool_stride, h // cfg.pool_stride, c),
                           jnp.float32)
            _stage_fwd(y, b)
            _stage_bwd(y, b, gz)
        _head_both(
            jnp.zeros((mb, h3, h3, cfg.c2_kernels), jnp.float32), params["fc"],
            jnp.zeros((mb,), jnp.int32), batch,
        )

    def step(params, images, labels):
        params = jax.tree.map(to_device, params)
        images = to_device(images, np.float32)
        labels = to_device(labels)
        batch = images.shape[0]
        slices = cluster.microbatch_slices(batch)
        for sl in slices:
            _warm(sl.stop - sl.start, batch, params)

        db = {0: None, 1: None}       # conv bias grads, summed over microbatches
        fc_grad = [None]              # fc param grads (a pytree), ditto

        def make_between(k, bias):
            def f(y):
                z = _stage_fwd(y, bias)

                def pull(gz):
                    gy, gb = _stage_bwd(y, bias, gz)
                    db[k] = gb if db[k] is None else db[k] + gb
                    return gy

                return z, pull
            return f

        def head(z, i):
            lbl = lax.slice_in_dim(labels, slices[i].start, slices[i].stop)
            loss_i, correct_i, gz, gfc = _head_both(z, params["fc"], lbl, batch)
            fc_grad[0] = gfc if fc_grad[0] is None else jax.tree.map(
                jnp.add, fc_grad[0], gfc
            )
            return (loss_i, correct_i), gz

        between = [
            make_between(0, params["conv1"]["bias"]),
            make_between(1, params["conv2"]["bias"]),
        ]

        def update(w, dw):
            with span("cnn.update"):
                return _sgd(w, dw)

        kernels = [params["conv1"]["kernel"], params["conv2"]["kernel"]]
        (k1, k2), res = cluster.conv_train_step(
            images, kernels, between, head, update=update,
        )
        with span("cnn.update"):
            rest = _sgd(
                (params["conv1"]["bias"], params["conv2"]["bias"], params["fc"]),
                (db[0], db[1], fc_grad[0]),
            )
        new_params = {
            "conv1": {"kernel": k1, "bias": rest[0]},
            "conv2": {"kernel": k2, "bias": rest[1]},
            "fc": rest[2],
        }
        aux = to_host(_stack(res.head_aux))
        loss = sum(float(v) for v in aux[:, 0])
        return new_params, loss, sum(float(v) for v in aux[:, 1]) / batch

    return step
