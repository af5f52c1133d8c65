"""The mesh path: the paper's kernel split as GSPMD shardings,
``core.conv_shard.make_sharded_train_step`` on a ("data", "model") mesh
of the traffic file's shape.  Each device holds ``C / n`` kernels of
each conv layer and sees the whole batch; the gather-mode rules pin each
conv output back to replicated, so the feature maps are all-gathered
over the devices in every step."""
from __future__ import annotations

import contextlib
import math


class Path:
    """The system under test for one run: ``place`` the seeded inputs,
    then ``step`` is the entry the window drives."""

    def __init__(self, cfg: dict, traffic: dict, devices):
        import jax

        from repro.compat import mesh_context
        from repro.core.conv_shard import make_sharded_train_step
        from repro.launch.mesh import make_mesh
        from repro.models.registry import rules_for_mode

        shape = tuple(traffic["mesh"])
        self.n = math.prod(shape)
        self.devices = list(devices[:self.n])
        self.cfg, self.batch = cfg, traffic["batch"]
        mesh = make_mesh(shape, ("data", "model"), self.devices)
        self._step, self.shardings = make_sharded_train_step(
            program_config(cfg), mesh, rules_for_mode(traffic["rules"]),
            self.batch, lr=traffic["lr"],
        )
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(mesh_context(mesh))
        self._jax = jax

    def place(self, params, images, labels):
        """Parameters on their shardings, and the ring of batches on the
        input shardings: ``(params, [(images, labels), ...])``."""
        put = self._jax.device_put
        psh, ish, lsh = self.shardings
        feed = [(put(images[i], ish), put(labels[i], lsh)) for i in range(images.shape[0])]
        return put(params, psh), feed

    def step(self, params, images, labels):
        new, loss, _acc = self._step(params, images, labels)
        return new, loss

    def step_conv_widths(self):
        """``[(device, {layer: kernels}, calls)]`` of the conv work the
        next step puts on each chip."""
        widths = {"conv1": self.cfg["c1_kernels"] // self.n,
                  "conv2": self.cfg["c2_kernels"] // self.n}
        return [(d.id, widths, 1) for d in self.devices]

    with_input_dx = False  # autodiff never differentiates the images

    def counters(self) -> dict:
        return {}

    def close(self) -> None:
        self._stack.close()


def program_config(cfg: dict):
    """The program's config object for a configuration file's sizes."""
    from repro.configs.base import CNNConfig

    return CNNConfig(
        arch_id=cfg["name"], c1_kernels=cfg["c1_kernels"], c2_kernels=cfg["c2_kernels"],
        kernel_size=cfg["kernel_size"], image_size=cfg["image_size"],
        image_channels=cfg["image_channels"], num_classes=cfg["num_classes"],
        pool_stride=cfg["pool_stride"], dtype=cfg["dtype"],
    )
