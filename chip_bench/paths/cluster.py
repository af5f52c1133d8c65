"""The cluster path: ``models.cnn.make_cluster_train_step`` over a
``HeteroCluster`` with the members, pipelined microbatches and Eq. 1
probe (at the conv1 shape) of ``launch.hetero.run_hetero(train_pipeline=True)``.
Two settings differ from that launcher's and come from the traffic
file: ``comp_aware`` (the launcher leaves it on), and ``probe_times``,
which replace the probe's readings once the probe has run, so that
every run splits the kernels into the same shard widths.
Members whose backend runs on the host CPU are named in ``HOST``; the
others compute on the chip, all on JAX's default device."""
from __future__ import annotations

import numpy as np

HOST = ("numpy",)


class Path:
    """The system under test for one run: ``place`` the seeded inputs,
    then ``step`` is the entry the window drives."""

    def __init__(self, cfg: dict, traffic: dict, devices):
        from repro.core.master_slave import HeteroCluster
        from repro.models.cnn import make_cluster_train_step

        from chip_bench.paths.mesh import program_config

        members = list(traffic["members"])
        self.cfg, self.batch = cfg, traffic["batch"]
        self.device = devices[0]
        self.on_chip = [m.partition(":")[0] not in HOST for m in members]
        self.cluster = HeteroCluster(
            [1.0] * len(members), members, pipeline=True,
            microbatches=traffic["microbatches"], partition=traffic["partition"],
            weight_cache=traffic["weight_cache"], comp_aware=traffic["comp_aware"],
            transport="inproc",
        )
        try:
            self.cluster.probe(
                image_size=cfg["image_size"], in_channels=cfg["image_channels"],
                kernel_size=cfg["kernel_size"], num_kernels=max(8, cfg["c1_kernels"]),
                batch=self.batch,
            )
            self.probed = list(self.cluster.probe_times)
            self.cluster.probe_times = [float(t) for t in traffic["probe_times"]]
            self._step = make_cluster_train_step(
                self.cluster, program_config(cfg), lr=traffic["lr"])
            self.cluster.reset_stats()
        except BaseException:
            self.cluster.shutdown()
            raise
        self.calls = len(self.cluster.microbatch_slices(self.batch))

    def place(self, params, images, labels):
        """Parameters stay on the device; the ring of batches is handed
        over as host arrays, as a data loader would."""
        images, labels = np.asarray(images), np.asarray(labels)
        return params, [(images[i], labels[i]) for i in range(images.shape[0])]

    def step(self, params, images, labels):
        new, loss, _acc = self._step(params, images, labels)
        return new, loss

    def step_conv_widths(self):
        """``[(device, {layer: kernels}, calls)]`` of the conv work the
        next step puts on the chip: the kernel counts that Eq. 1 gives
        the chip members now (a step's plans are cut from these)."""
        counts = {layer: self.cluster.shares_for(self.cfg[key])
                  for layer, key in (("conv1", "c1_kernels"), ("conv2", "c2_kernels"))}
        widths = {layer: int(sum(c for c, chip in zip(cs, self.on_chip) if chip))
                  for layer, cs in counts.items()}
        return [(self.device.id, widths, self.calls)]

    def note(self) -> str:
        """The probe's readings and the shard widths they gave, for the log."""
        widths = {k: self.cluster.shares_for(self.cfg[k]).tolist()
                  for k in ("c1_kernels", "c2_kernels")}
        return f"probe read {self.probed}, split by {self.cluster.probe_times}: {widths}"

    with_input_dx = True  # the backward chain returns dX of its input

    def counters(self) -> dict:
        return {"gather_wait_s": self.cluster.timing.gather_wait_s}

    def close(self) -> None:
        self.cluster.shutdown()
