#!/usr/bin/env python3
"""The control and the planted faults that the comparison of
``reference.py`` has to fail, and the readings that set its limits, for
the CNN's cells (``archs/cnn.py``).

The configuration states fp32 at matmul precision "highest".  The
control is the reference one step down, at "high": every product of the
convolutions and the fc layer in three bf16 passes (hi*hi + hi*lo +
lo*hi, fp32 sums), forward and backward.  It is written out here rather
than asked of the compiler, so it computes the same on any platform
(a CPU ignores the precision it is asked for); the kind ``high`` asks
the compiler instead, for comparison on the chip.

The faults, each a training step broken where a later change could
break it:

- ``unchanged``: the step returns its state unchanged;
- ``half_batch``: half of the batch is left out and the mean taken over
  the rest;
- ``no_exchange``: the exchange between members is left out: each conv
  output keeps only the first of ``members`` equal kernel shards, the
  others read zero.

    python3 chip_bench/control.py --workload cnn500_cluster_chip \\
        --kinds high half_batch no_exchange --seeds 11 12 13 --seconds 2

runs on the chip, for each seed and kind, the harness's own run of the
cell (``run.run_cell``: its batch, ring, lr, checked steps and a short
window) with that kind in the program's place, and prints the result
line the harness prints, under ``line``, with the kind, seed, lr, batch
and member count.  The cell's limits have to read it as not correct.
The kind ``program`` is the cell's own system under test: its readings
over a dozen seeds or more, in one process, are the lower readings the
limits are set above.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chip_bench import reference  # noqa: E402
from chip_bench.archs import cnn  # noqa: E402


def _split(a):
    """``(hi, lo)``: ``a`` rounded to bf16, and the rest rounded to bf16.
    ``reduce_precision`` and not a round trip through ``astype``: XLA may
    drop an f32 -> bf16 -> f32 pair as excess precision, in one fusion
    and not another, and the passes would then no longer add up."""
    from jax import lax

    hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)


def three_pass(f):
    """A bilinear ``f(a, b)`` (at fp32 "highest") computed, forward and
    backward, as TPU "high" does: hi*hi + hi*lo + lo*hi in bf16 parts."""
    import jax

    def passes(g, a, b):
        (ah, al), (bh, bl) = _split(a), _split(b)
        return g(ah, bh) + g(ah, bl) + g(al, bh)

    @jax.custom_vjp
    def h(a, b):
        return passes(f, a, b)

    def fwd(a, b):
        return h(a, b), (a, b)

    def bwd(res, ct):
        a, b = res
        da = passes(lambda c, bb: jax.vjp(lambda aa: f(aa, bb), a)[1](c)[0], ct, b)
        db = passes(lambda aa, c: jax.vjp(lambda bb: f(aa, bb), b)[1](c)[0], a, ct)
        return da, db

    h.defvjp(fwd, bwd)
    return h


def _highest(f):
    return lambda a, b: f(a, b, reference.precision_of("highest"))


_HIGH_CONV = three_pass(_highest(cnn.plain_conv))
_HIGH_DOT = three_pass(_highest(cnn.plain_dot))


def high_conv(x, w, _precision):
    """The control's convolution."""
    return _HIGH_CONV(x, w)


def high_dot(x, w, _precision):
    """The control's fc product."""
    return _HIGH_DOT(x, w)


def control_step(cfg, lr):
    """The reference step at "high"."""
    return cnn.reference_step(cfg, lr, conv=high_conv, dot=high_dot)


def exchange_conv(members: int):
    """A conv whose output keeps the first of ``members`` kernel shards."""
    import functools

    return functools.partial(_first_shard_conv, members=members)


def _first_shard_conv(x, w, precision, members):
    import jax.numpy as jnp

    y = cnn.plain_conv(x, w, precision)
    keep = -(-w.shape[-1] // members)
    mask = jnp.arange(w.shape[-1]) < keep
    return y * mask


class ReferencePath:
    """A stand-in for the system under test, built from the reference
    (optionally broken), that the harness drives like a path module.
    ``kind``: ``reference``, ``control``, ``unchanged``, ``half_batch``,
    ``no_exchange`` (with ``members``), or ``high``: the reference at
    that precision as the compiler gives it (on a TPU; a CPU computes it
    in full fp32)."""

    with_input_dx = False

    def __init__(self, cfg, traffic, devices, kind="reference", members=2):
        lr = traffic["lr"]
        self.cfg, self.kind, self.device = cfg, kind, devices[0]
        if kind == "control":
            self._step = control_step(cfg, lr)
        elif kind == "no_exchange":
            self._step = cnn.reference_step(cfg, lr, conv=exchange_conv(members))
        elif kind == "high":
            self._step = cnn.reference_step(cfg, lr, kind)
        else:
            self._step = cnn.reference_step(cfg, lr)

    def place(self, params, images, labels):
        return params, [(images[i], labels[i]) for i in range(images.shape[0])]

    def step(self, params, images, labels):
        if self.kind == "half_batch":
            half = images.shape[0] // 2
            return self._step(params, images[:half], labels[:half])
        new, loss = self._step(params, images, labels)
        if self.kind == "unchanged":
            return params, loss
        return new, loss

    def step_conv_widths(self):
        return [(self.device.id, {"conv1": self.cfg["c1_kernels"],
                                  "conv2": self.cfg["c2_kernels"]}, 1)]

    def counters(self) -> dict:
        return {}

    def close(self) -> None:
        pass


def exchange_members(traffic: dict) -> int:
    """How many members or chips a cell's conv output is split over."""
    if "members" in traffic:
        return len(traffic["members"])
    return math.prod(traffic.get("mesh", [1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--kinds", nargs="+", default=["high", "half_batch", "no_exchange"])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from chip_bench import run

    cell = run.Cell.load(args.workload, trace=False)
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision", cell.cfg["matmul_precision"])
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"JAX's default device is {devices[0].platform!r}, not a TPU")
    devices = devices[:cell.chips]
    members = exchange_members(cell.traffic)
    for seed in args.seeds:
        for kind in args.kinds:
            def path_cls(cfg, traffic, devs, kind=kind):
                return ReferencePath(cfg, traffic, devs, kind, members)

            if kind == "program":
                path_cls = cell.path_class()

            meas, checks, failed, peak = run.run_cell(
                cell, path_cls, devices, seed, args.seconds, False)
            line = run.result_line(cell, meas, checks, failed, peak, devices, False)
            print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                              "lr": cell.traffic["lr"], "batch": cell.traffic["batch"],
                              "members": members, "line": line}), flush=True)
    return 0


if __name__ == "__main__":
    from chip_bench import run

    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    run.exit_now(code)
