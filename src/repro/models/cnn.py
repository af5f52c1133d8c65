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

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
    # residuals across the pipeline
    _stage_fwd = jax.jit(_stage)
    _stage_bwd = jax.jit(lambda y, b, gz: jax.vjp(_stage, y, b)[1](gz))

    @jax.jit
    def _head_both(z, fc, labels, denom):
        (loss, correct), vjp = jax.vjp(
            lambda zz, f: _head_sums(zz, f, labels, denom), z, fc
        )
        gz, gfc = vjp((jnp.ones((), jnp.float32), jnp.zeros((), jnp.float32)))
        return loss, correct, gz, gfc

    warmed: set = set()  # microbatch sizes whose jits are compiled

    def _warm(mb, params):
        """Compile the master-only jits for this microbatch size OUTSIDE
        the pipeline: one-time compilation must not pollute the cluster's
        measured non-conv duty (it would strip the master's conv share)."""
        if mb in warmed:
            return
        warmed.add(mb)
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
            jnp.zeros((mb,), jnp.int32), jnp.float32(1.0),
        )

    def step(params, images, labels):
        images = to_host(images, np.float32)
        labels = to_host(labels)
        batch = images.shape[0]
        slices = cluster.microbatch_slices(batch)
        for sl in slices:
            _warm(sl.stop - sl.start, params)

        db = {0: None, 1: None}       # conv bias grads, summed over microbatches
        fc_grad = [None]              # fc param grads (a pytree), ditto

        def make_between(k, bias):
            def f(y):
                y = to_device(y)
                z = _stage_fwd(y, bias)

                def pull(gz):
                    gy, gb = _stage_bwd(y, bias, to_device(gz, jnp.float32))
                    gb = to_host(gb)
                    db[k] = gb if db[k] is None else db[k] + gb
                    return to_host(gy, np.float32)

                return to_host(z, np.float32), pull
            return f

        def head(z, i):
            lbl = to_device(labels[slices[i]])
            loss_i, correct_i, gz, gfc = _head_both(
                to_device(z), params["fc"], lbl, jnp.float32(batch)
            )
            fc_grad[0] = gfc if fc_grad[0] is None else jax.tree.map(
                jnp.add, fc_grad[0], gfc
            )
            return ((float(to_host(loss_i)), float(to_host(correct_i))),
                    to_host(gz, np.float32))

        def update(w, dw):
            with span("cnn.update"):
                return w - lr * dw

        between = [
            make_between(0, params["conv1"]["bias"]),
            make_between(1, params["conv2"]["bias"]),
        ]
        with span("cnn.update"):
            kernels = [
                to_host(params["conv1"]["kernel"], np.float32),
                to_host(params["conv2"]["kernel"], np.float32),
            ]
        new_kernels, res = cluster.conv_train_step(
            images, kernels, between, head, update=update,
        )

        loss = float(sum(a[0] for a in res.head_aux))
        acc = float(sum(a[1] for a in res.head_aux)) / batch
        with span("cnn.update"):
            new_params = {
                "conv1": {
                    "kernel": to_device(new_kernels[0]),
                    "bias": params["conv1"]["bias"] - to_device(lr * db[0]),
                },
                "conv2": {
                    "kernel": to_device(new_kernels[1]),
                    "bias": params["conv2"]["bias"] - to_device(lr * db[1]),
                },
                "fc": jax.tree.map(lambda p, g: p - lr * g, params["fc"], fc_grad[0]),
            }
        return new_params, loss, acc

    return step
