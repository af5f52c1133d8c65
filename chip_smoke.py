#!/usr/bin/env python3
"""Smoke run of the paper's main path on a TPU, through the entry points
a user calls.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the kernel-axis mesh step on four

One chip, in one process (a chip belongs to one process at a time):

1. kernel precision: the Pallas conv fwd/dX/dW kernels at a conv2 shard
   shape against the lax conv at HIGHEST precision, once under the
   default matmul precision and once under "highest";
2. training: 3 pipelined steps of ``launch.hetero.run_hetero`` at
   cifar_cnn_500_1500 (C1=500, C2=1500, 5x5, 32x32x3 images), batch 128,
   kernel-axis split, over a heterogeneous in-process cluster: an
   ``xla`` master and a ``pallas`` member on the chip plus a ``numpy``
   member on the host CPU.  The first step's loss and updated conv
   kernels are checked against the same SGD step in plain single-device
   jax (``cnn_loss`` with ``apply_conv``);
3. serving: ``launch.hetero.run_serve`` at the same widths answers 8
   requests of 32x32 images, every one ``ok``.

``--chips 4`` runs only the paper's kernel split as GSPMD shardings
(``core.conv_shard.make_sharded_train_step``) on a ("data", "model") =
(1, 4) mesh, checks that each conv kernel is spread over the four
devices, and compares the step with the same step on one chip.

Both sides of every comparison run fp32 with the matmul precision pinned
to "highest", so they differ only in summation order: the loss must
agree to 1e-4 relative, the Pallas kernels to 1e-4 of their largest
output.  An updated conv kernel must agree in L2 norm to 1e-3 of the
reference update, and everywhere to 1e-2 of its largest entry.  The
loose max-abs limit is for max-pool near-ties: a change of summation
order can flip which of two (nearly) equal pre-pool values wins, which
routes one pixel's gradient term to a neighbour and moves the dW
entries of that kernel by about one term of a sum over B*H*W pixels.
The L2 limit still catches an error spread over every kernel, such as
an unpinned single-pass bf16 matmul.

Data and weights come from fixed seeds.  The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``; a
failed phase, or a default device that is not a TPU, exits non-zero
without it.  The compile cache follows ``repro.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

C1, C2, BATCH, STEPS, LR = 500, 1500, 128, 3, 0.05
BACKENDS = ["xla", "pallas", "numpy"]  # master, chip member, host-CPU member
REQUESTS = 8
LOSS_RTOL, KERNEL_RTOL = 1e-4, 1e-4
UPDATE_MAX_RTOL, UPDATE_L2_RTOL = 1e-2, 1e-3


def log(key, value) -> None:
    print(f"[chip_smoke] {key}: {value}", flush=True)


def rel_err(got, want) -> float:
    """max|got - want| / max|want|."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def check_update(name, w0, got, want) -> None:
    """Compare an updated kernel with the reference update; raise beyond
    the module's stated limits."""
    import numpy as np

    w0, got, want = (np.asarray(a, np.float64) for a in (w0, got, want))
    diff, update = got - want, want - w0
    scale = float(np.max(np.abs(update)))
    max_err = float(np.max(np.abs(diff))) / scale
    l2_err = float(np.linalg.norm(diff) / np.linalg.norm(update))
    per_kernel = np.max(np.abs(diff), axis=(0, 1, 2)) / scale
    log(f"{name} update diff", {
        "max_abs_over_largest_update": max_err, "l2_rel": l2_err,
        "kernels_over_1e-4": f"{int(np.sum(per_kernel > 1e-4))} of {per_kernel.size}",
    })
    if not (max_err <= UPDATE_MAX_RTOL and l2_err <= UPDATE_L2_RTOL):
        raise AssertionError(
            f"{name}: updated kernel differs by {max_err} (max) / {l2_err} (l2)"
        )


def reference_step(cfg, lr):
    """The same SGD step in plain single-device jax."""
    import jax

    from repro.models.cnn import cnn_loss

    @jax.jit
    def step(params, images, labels):
        (loss, _), grads = jax.value_and_grad(
            lambda p: cnn_loss(p, images, labels, cfg=cfg), has_aux=True
        )(params)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss

    return step


def kernel_precision_phase(batch=8, hw=16, cin=C1, cout=256, interpret=False):
    """Pallas conv fwd/dX/dW against lax at HIGHEST; returns the relative
    errors under the default and the "highest" matmul precision.  Only
    "highest" is held to a limit: the default shows what the kernel's
    in-kernel f32 dot does unpinned."""
    import jax

    from repro.kernels.conv2d import conv2d_dw_pallas, conv2d_dx_pallas, conv2d_pallas

    ks = jax.random.split(jax.random.key(7), 3)
    x = jax.random.normal(ks[0], (batch, hw, hw, cin))
    w = jax.random.normal(ks[1], (5, 5, cin, cout)) * 0.05
    g = jax.random.normal(ks[2], (batch, hw, hw, cout))

    def ref(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
        )

    y_ref, pullback = jax.vjp(ref, x, w)
    dx_ref, dw_ref = pullback(g)
    errs = {}
    for mode in ("default", "highest"):
        with jax.default_matmul_precision(mode):
            y = conv2d_pallas(x, w, interpret=interpret)
            dx = conv2d_dx_pallas(g, w, interpret=interpret)
            dw = conv2d_dw_pallas(x, g, 5, 5, interpret=interpret)
        errs[mode] = {
            "fwd": rel_err(y, y_ref), "dx": rel_err(dx, dx_ref), "dw": rel_err(dw, dw_ref),
        }
        log(f"pallas vs lax-HIGHEST rel err, {mode} precision", errs[mode])
    worst = max(errs["highest"].values())
    if not worst <= KERNEL_RTOL:
        raise AssertionError(f"pallas kernels at highest precision: rel err {worst}")
    return errs


def train_phase(backends=BACKENDS, c1=C1, c2=C2, batch=BATCH, steps=STEPS):
    """``run_hetero`` training steps over the heterogeneous cluster, the
    first checked against ``reference_step``."""
    import numpy as np

    from repro.launch.hetero import run_hetero, train_inputs
    from repro.models.cnn import make_cnn_config

    after = {}
    rec = run_hetero(
        [1.0] * len(backends), backends, train_pipeline=True, partition="kernel",
        transport="inproc", c1=c1, c2=c2, batch=batch, steps=steps, lr=LR,
        on_step=lambda i, params, loss: after.setdefault(i, params),
    )
    losses = rec["losses"]
    log("probe seconds per member", rec["probe_s"])
    log("set-up seconds (cluster start + Eq. 1 probe)", rec["setup_s"])
    log("step seconds (the first compiles every shard shape)", rec["step_s"])
    log("losses", losses)
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise AssertionError(f"training losses not finite: {losses}")

    cfg = make_cnn_config(c1, c2)
    params, images, labels = train_inputs(cfg, batch)
    step = reference_step(cfg, LR)
    t0 = time.perf_counter()
    ref_params, ref_loss = step(params, images, labels)
    ref_losses = [float(ref_loss)]
    log("reference step seconds (compile included)", time.perf_counter() - t0)
    p = ref_params
    for _ in range(1, steps):
        p, loss = step(p, images, labels)
        ref_losses.append(float(loss))
    log("reference losses", ref_losses)
    loss_err = abs(losses[0] - ref_losses[0]) / abs(ref_losses[0])
    log("first-step loss rel diff", loss_err)
    if not loss_err <= LOSS_RTOL:
        raise AssertionError(f"first-step loss rel diff {loss_err} > {LOSS_RTOL}")
    for name in ("conv1", "conv2"):
        check_update(
            name, params[name]["kernel"], after[0][name]["kernel"],
            ref_params[name]["kernel"],
        )
    return rec


def serve_phase(backends=BACKENDS, c1=C1, c2=C2, requests=REQUESTS):
    """``run_serve`` answers ``requests`` 32x32 requests, all ``ok``.
    The deadline only bounds a hang: the first batches compile."""
    from repro.launch.hetero import run_serve

    rec = run_serve(
        [1.0] * len(backends), backends, c1=c1, c2=c2, requests=requests,
        image_size=32, max_batch=4, deadline_s=600.0,
    )
    log("serve probe seconds per member", rec["probe_s"])
    log("serve statuses", rec["statuses"])
    log("serve wall seconds (compile included)", rec["wall_s"])
    if rec["requests"] != requests or not rec["all_ok"]:
        raise AssertionError(f"serving: statuses {rec['statuses']}")
    return rec


def mesh_phase(devices, c1=C1, c2=C2, batch=BATCH):
    """The kernel-axis GSPMD step on a (1, len(devices)) mesh against the
    same step on one device."""
    import jax
    import numpy as np

    from repro.compat import mesh_context
    from repro.core.conv_shard import make_sharded_train_step
    from repro.launch.hetero import train_inputs
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import make_cnn_config
    from repro.models.registry import rules_for_mode

    n = len(devices)
    cfg = make_cnn_config(c1, c2)
    params, images, labels = train_inputs(cfg, batch)
    mesh = make_mesh((1, n), ("data", "model"), devices)
    step, (param_sh, image_sh, label_sh) = make_sharded_train_step(
        cfg, mesh, rules_for_mode("gather"), batch, lr=LR
    )
    with mesh_context(mesh):
        p, x, y = (
            jax.device_put(a, s)
            for a, s in ((params, param_sh), (images, image_sh), (labels, label_sh))
        )
        t0 = time.perf_counter()
        new, loss, _ = jax.block_until_ready(step(p, x, y))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(step(new, x, y))
        steady = time.perf_counter() - t0
    log(f"{n}-device step seconds: first (compile included), second", [first, steady])

    for name, cout in (("conv1", c1), ("conv2", c2)):
        k = new[name]["kernel"]
        shards = k.addressable_shards
        placed = sorted({s.device.id for s in shards})
        widths = sorted({s.data.shape[-1] for s in shards})
        log(f"{name} kernel", f"spec={k.sharding.spec} devices={placed} shard widths={widths}")
        if len(placed) != n or widths != [cout // n]:
            raise AssertionError(f"{name} kernel is not split over {n} devices")

    one = jax.sharding.SingleDeviceSharding(devices[0])
    ref_new, ref_loss = reference_step(cfg, LR)(*jax.device_put((params, images, labels), one))
    loss, ref_loss = float(loss), float(ref_loss)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    log(f"loss: {n} devices, one device, rel diff", [loss, ref_loss, loss_err])
    if not loss_err <= LOSS_RTOL:
        raise AssertionError(f"mesh loss rel diff {loss_err} > {LOSS_RTOL}")
    for name in ("conv1", "conv2"):
        check_update(
            name, params[name]["kernel"], np.asarray(new[name]["kernel"]),
            ref_new[name]["kernel"],
        )


def clean_exit(code: int) -> None:
    """Leave through ``os._exit`` after flushing, like the CLI: runtime
    threads must not hang interpreter finalization."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: kernels, training and serving on one chip; "
                         "4: only the kernel-axis mesh step on four chips")
    args = ap.parse_args()
    try:
        from repro.compile_cache import configure_compile_cache

        log("compile cache", configure_compile_cache())
        import jax

        devices = jax.devices()
        dev = devices[0]
        if dev.platform != "tpu":
            print(f"[chip_smoke] no TPU: JAX's default device is {dev.platform!r}",
                  file=sys.stderr)
            clean_exit(1)
        if len(devices) < args.chips:
            print(f"[chip_smoke] --chips {args.chips} needs that many devices, "
                  f"found {len(devices)}", file=sys.stderr)
            clean_exit(1)
        log("device", f"{dev.platform} {dev.device_kind} x{len(devices)}")
        jax.config.update("jax_default_matmul_precision", "highest")
        if args.chips == 1:
            kernel_precision_phase()
            train_phase()
            serve_phase()
        else:
            mesh_phase(devices[:args.chips])
        stats = dev.memory_stats() or {}
        log("peak_bytes_in_use (device 0)", stats.get("peak_bytes_in_use", "not reported"))
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
        }}))
    except BaseException:
        traceback.print_exc()
        clean_exit(1)
    clean_exit(0)


if __name__ == "__main__":
    main()
