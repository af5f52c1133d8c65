"""Launch the paper's CNN over the emulated heterogeneous cluster.

The one CLI that wires the whole stack together: per-device compute
backends (core/backends.py), Eq. 1 probing/partitioning, and the
asynchronous pipelined scatter/gather protocol (core/master_slave.py),
driving real training steps of the CIFAR CNN (models/cnn.py).

    PYTHONPATH=src python -m repro.launch.hetero \
        --slowdowns 1.0,1.5,3.0 --backends numpy,xla,numpy \
        --pipeline --microbatches 4 --steps 2

Device 0 is the master; keep its backend ``numpy`` (the training loop
drives the cluster through jax host callbacks — see master_slave.py).

``--train-pipeline`` switches to the activation-stashing full-step
schedule (``conv_train_step``): forward AND backward of every conv layer
are pipelined across the cluster and the master-only stages overlap
slave compute.  It drives the cluster directly (no jax callbacks), so
any master backend is safe, and the comp-aware partitioner discounts the
master's measured non-conv duty automatically:

    PYTHONPATH=src python -m repro.launch.hetero \
        --slowdowns 1.0,1.5,3.0 --train-pipeline --microbatches 4 --steps 4

``--partition`` picks the conv split axis — ``kernel`` (the paper),
``spatial`` (height strips + halo exchange: each slave receives only its
rows instead of the full activation), ``batch`` (data parallelism:
replicate the kernel, split the batch's N axis, sum per-slave dW — wins
on fat links), or ``auto`` (per layer, the axis with the smallest
predicted wall-clock over the emulated links) — and
``--wire-dtype fp16|bf16`` turns on the compact wire codec.  Both need
``--bandwidth-mbps`` to matter (with infinitely fast links the wire is
free and auto sticks to the paper's kernel axis):

    PYTHONPATH=src python -m repro.launch.hetero \
        --slowdowns 1.0,1.5,3.0 --train-pipeline --bandwidth-mbps 50 \
        --partition auto --wire-dtype fp16 --steps 4

``--transport tcp`` runs every slave as a REAL OS process connected over
localhost sockets (core/cluster/transport.py): comm, serialization and
slave compute are measured, not emulated, and the probe feeds each
link's measured bandwidth to the comm-aware partitioner:

    PYTHONPATH=src python -m repro.launch.hetero \
        --transport tcp --train-pipeline --slowdowns 1.0,1.5 --steps 2

``--transport shm`` keeps the OS-subprocess slaves but moves the bulk
array bytes through zero-copy shared-memory rings (same host only;
control frames stay on a localhost socket).  ``--wire-codec`` layers
the pluggable compressor stack over any transport with a per-message-
class spec, and the versioned weight-broadcast cache is on by default
(``--no-weight-cache`` to disable):

    PYTHONPATH=src python -m repro.launch.hetero \
        --transport shm --train-pipeline --slowdowns 1.0,1.5 \
        --wire-codec "weights=fp16,acts=int8,grads=topk:0.05" --steps 2

``--groups GxM`` trades the flat topology for the TWO-TIER hierarchy
(core/cluster/hierarchy.py): G sub-master groups of M devices each,
the root planning disjoint batch rows across groups (exact dW
all-reduce) while each group partitions its rows internally on
``--group-partition``.  ``--slowdowns`` then carries 1 + G*M entries
(root first, then group devices chunked M per group) or just the root;
``--master-nic-mbps`` emulates one shared master port serialized
across all root links (inproc only) — the regime where two tiers beat
flat, because the root's ingress carries G summed group gradients
instead of G*M:

    PYTHONPATH=src python -m repro.launch.hetero \
        --groups 2x3 --train-pipeline --master-nic-mbps 200 --steps 4

``--expected-slaves N`` makes the master WAIT for N hand-launched
slaves instead of spawning them — the remote-host path.  Pass only the
master's ``--slowdowns`` entry, bind with ``--listen-host``/
``--listen-port``, export the same REPRO_CLUSTER_AUTH hex token in
both environments, and start each slave (any reachable host) with:

    python -m repro.core.cluster.protocol --host MASTER --port P \
        --backend numpy --heartbeat-s 0.5

``--heartbeat-s`` arms liveness on tcp: slaves beat small frames and
the master declares a silent link dead after 3x the interval, evicts
it, absorbs its in-flight shards, and re-partitions the next step over
the survivors (core/cluster/cluster.py, the elastic runtime).

The CLI always leaves through ``os._exit`` after flushing its output:
an ``xla`` slave (or any backend with native runtime threads) used to
complete its steps and then hang the interpreter at exit (XLA runtime
thread vs CPython finalization, the ROADMAP pre-existing bug).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core.master_slave import HeteroCluster, make_distributed_conv
from repro.core.partitioner import workload_shares
from repro.models.cnn import (
    cnn_loss,
    init_cnn,
    make_cluster_train_step,
    make_cnn_config,
)


def train_inputs(cfg, batch: int):
    """The seeded initial parameters, images and labels every training
    run starts from: ``(params, images, labels)``."""
    params = init_cnn(jax.random.key(0), cfg)
    imgs = jax.random.normal(
        jax.random.key(1), (batch, cfg.image_size, cfg.image_size, cfg.image_channels)
    )
    labels = jnp.arange(batch) % cfg.num_classes
    return params, imgs, labels


def run_hetero(
    slowdowns,
    backends=None,
    *,
    pipeline: bool = False,
    train_pipeline: bool = False,
    microbatches: int = 4,
    c1: int = 8,
    c2: int = 16,
    batch: int = 8,
    steps: int = 2,
    lr: float = 0.05,
    partition: str = "kernel",
    wire_dtype=None,
    wire_codec=None,
    weight_cache: bool = True,
    bandwidth_mbps=None,
    transport: str = "inproc",
    expected_slaves=None,
    listen_host: str = "127.0.0.1",
    listen_port: int = 0,
    heartbeat_s=None,
    groups=None,
    group_partition: str = "auto",
    master_nic_mbps=None,
    on_step=None,
) -> dict:
    """Train ``steps`` SGD steps of the CNN over a cluster built from
    ``slowdowns``/``backends`` and return the run's record: probe times,
    per-step losses, set-up seconds (cluster start-up and Eq. 1 probe)
    apart from each step's seconds (the first includes compiling every
    shard shape), and the timing breakdown.  ``on_step(i, params,
    loss)``, if given, sees the parameters after each step."""
    t_start = time.perf_counter()
    if not train_pipeline and backends is not None and backends[0] != "numpy":
        # the callback training loop re-enters jax on the blocked runtime
        # thread with a non-numpy master and can deadlock — fail fast
        # (make_distributed_conv raises too; this gives the CLI message)
        raise SystemExit(
            f"device 0 (the master) must use the 'numpy' backend with "
            f"callback-driven training, got {backends[0]!r}; slaves may "
            f"use any backend.  --train-pipeline drives the cluster "
            f"directly and lifts this restriction."
        )
    cfg = make_cnn_config(c1, c2)
    if groups is not None:
        from repro.core.cluster.hierarchy import (
            HierarchicalCluster,
            parse_groups,
        )

        if expected_slaves is not None:
            raise SystemExit(
                "--groups spawns its own sub-masters; --expected-slaves "
                "(hand-launched joins) is a flat-cluster feature"
            )
        gspecs = parse_groups(
            groups,
            slowdowns=slowdowns[1:] if len(slowdowns) > 1 else None,
            backends=backends[1:] if backends and len(backends) > 1 else None,
            partition=group_partition,
            pipeline=pipeline or train_pipeline,
            microbatches=microbatches,
        )
        cluster = HierarchicalCluster(
            gspecs,
            master_slowdown=slowdowns[0],
            master_backend=backends[0] if backends else "numpy",
            pipeline=pipeline or train_pipeline, microbatches=microbatches,
            wire_dtype=wire_dtype, wire_codec=wire_codec,
            weight_cache=weight_cache, bandwidth_mbps=bandwidth_mbps,
            master_nic_mbps=master_nic_mbps, transport=transport,
            heartbeat_s=heartbeat_s,
        )
        partition = "batch"  # the root's inter-group axis, by construction
    else:
        cluster = HeteroCluster(
            slowdowns, backends,
            pipeline=pipeline or train_pipeline, microbatches=microbatches,
            partition=partition, wire_dtype=wire_dtype,
            wire_codec=wire_codec, weight_cache=weight_cache,
            bandwidth_mbps=bandwidth_mbps, transport=transport,
            expected_slaves=expected_slaves,
            listen_host=listen_host, listen_port=listen_port,
            heartbeat_s=heartbeat_s,
            master_nic_mbps=master_nic_mbps,
        )
    try:
        probe = cluster.probe(
            image_size=cfg.image_size, in_channels=cfg.image_channels,
            kernel_size=cfg.kernel_size, num_kernels=max(8, c1), batch=batch,
        )
        shares = workload_shares(probe)
        print(f"devices: slowdowns={list(cluster.slowdowns)} "
              f"backends={cluster.backends} transport={transport}"
              + (f" topology={groups} (groups plan rows internally on "
                 f"'{group_partition}')" if groups else ""))
        print(f"probe times: {np.round(probe, 4).tolist()}")
        if transport in ("tcp", "shm"):
            print(f"measured link bandwidth (Mbps): "
                  f"{[None if b is None else round(b, 1) for b in cluster.measured_bandwidths]}")
        print(f"Eq.1 shares: {np.round(shares, 3).tolist()} -> "
              f"c2 kernels {cluster.shares_for(c2).tolist()}")

        params, imgs, labels = train_inputs(cfg, batch)

        if train_pipeline:
            # full-step pipeline: fwd + bwd distributed, direct driver
            cluster_step = make_cluster_train_step(cluster, cfg, lr=lr)

            def train_step(p):
                p, loss, _acc = cluster_step(p, imgs, labels)
                return p, loss
        else:
            # seed path: jax custom-VJP conv via host callbacks
            conv_fn = make_distributed_conv(cluster)

            def train_step(p):
                (loss, acc), grads = jax.value_and_grad(
                    lambda q: cnn_loss(q, imgs, labels, cfg=cfg, conv_fn=conv_fn),
                    has_aux=True,
                )(p)
                return jax.tree.map(lambda a, g: a - lr * g, p, grads), loss

        cluster.reset_stats()
        setup_s = time.perf_counter() - t_start
        losses, step_s = [], []
        for i in range(steps):
            ts = time.perf_counter()
            params, loss = train_step(params)
            losses.append(float(loss))
            jax.block_until_ready(params)
            step_s.append(time.perf_counter() - ts)
            if on_step is not None:
                on_step(i, params, losses[-1])
        wall = sum(step_s)

        t = cluster.timing
        rec = {
            "protocol": (
                "trainstep-pipelined" if train_pipeline
                else "pipelined" if pipeline else "barrier"
            ),
            "transport": transport,
            "topology": groups or "flat",
            "group_partition": group_partition if groups else None,
            "master_nic_mbps": master_nic_mbps,
            "measured_bandwidth_mbps": list(cluster.measured_bandwidths),
            "microbatches": microbatches if (pipeline or train_pipeline) else 1,
            "partition": partition,
            "partition_choices": {
                str(k): v for k, v in cluster.partition_choices.items()
            },
            "wire_dtype": wire_dtype or "fp32",
            "wire_codec": cluster._codec_cfg.spec,
            "weight_cache": weight_cache,
            "bandwidth_mbps": bandwidth_mbps,
            "heartbeat_s": heartbeat_s,
            "slave_ids": list(cluster.slave_ids),
            "failures": list(cluster.failures),
            "comp_duty": cluster.comp_duty,
            "backends": list(cluster.backends),
            "probe_s": [float(x) for x in probe],
            "losses": losses,
            "setup_s": setup_s,
            "step_s": step_s,
            "wall_s": wall,
            "comm_mb": cluster.comm_bytes / 2 ** 20,
            "timing": dataclasses.asdict(t),
        }
        print(f"{steps} steps in {wall:.2f}s  losses={np.round(losses, 4).tolist()}")
        print(f"comm={rec['comm_mb']:.1f}MiB  scatter={t.comm_s:.3f}s "
              f"conv={t.conv_s:.3f}s wait={t.gather_wait_s:.3f}s "
              f"overlap={t.overlap_s:.3f}s")
        if train_pipeline:
            print(f"comp-aware: master non-conv duty={cluster.comp_duty:.2f} -> "
                  f"c2 kernels now {cluster.shares_for(c2).tolist()}")
        if partition == "auto" and cluster.partition_choices:
            print(f"auto partition picks: {rec['partition_choices']}")
        return rec
    finally:
        cluster.shutdown()


def run_serve(
    slowdowns,
    backends=None,
    *,
    microbatches: int = 4,
    c1: int = 8,
    c2: int = 16,
    requests: int = 20,
    deadline_s=30.0,
    max_batch: int = 4,
    image_size: int = 16,
    partition: str = "kernel",
    wire_dtype=None,
    wire_codec=None,
    weight_cache: bool = True,
    bandwidth_mbps=None,
    transport: str = "inproc",
    expected_slaves=None,
    listen_host: str = "127.0.0.1",
    listen_port: int = 0,
    heartbeat_s=None,
    seed: int = 0,
) -> dict:
    """Serve ``requests`` synthetic conv-chain requests through a
    ``ClusterServer`` (continuous batching over the pipelined cluster)
    and report throughput + tail latency.  Doubles as the CI
    serve-smoke: the returned record carries ``all_ok`` and the CLI
    exits nonzero unless every request completed under its deadline."""
    from repro.serve.server import ClusterServer

    rng = np.random.default_rng(seed)
    k = 5
    weights = [
        rng.standard_normal((k, k, 3, c1)).astype(np.float32) * 0.1,
        rng.standard_normal((k, k, c1, c2)).astype(np.float32) * 0.1,
    ]

    def _relu_pool(y):
        """Master-only stage after each conv: ReLU + 2x2 max-pool
        (numpy — the serve loop drives the cluster directly)."""
        y = np.maximum(y, 0.0)
        b, h, w, c = y.shape
        return y.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))

    feat = image_size // 4
    fc = rng.standard_normal((feat * feat * c2, 10)).astype(np.float32) * 0.01

    def _head(z):
        return z.reshape(z.shape[0], -1) @ fc

    cluster = HeteroCluster(
        slowdowns, backends,
        pipeline=True, microbatches=microbatches,
        partition=partition, wire_dtype=wire_dtype,
        wire_codec=wire_codec, weight_cache=weight_cache,
        bandwidth_mbps=bandwidth_mbps, transport=transport,
        expected_slaves=expected_slaves,
        listen_host=listen_host, listen_port=listen_port,
        heartbeat_s=heartbeat_s,
    )
    try:
        probe = cluster.probe(image_size=image_size, in_channels=3,
                              kernel_size=k, num_kernels=max(8, c1),
                              batch=max_batch)
        print(f"serving: slowdowns={list(cluster.slowdowns)} "
              f"backends={cluster.backends} transport={transport} "
              f"max_batch={max_batch} deadline_s={deadline_s}")
        server = ClusterServer(
            cluster, weights, between=[_relu_pool, _relu_pool], head=_head,
            max_batch=max_batch, max_queue=max(2 * requests, 16),
            default_deadline_s=deadline_s,
        )
        t0 = time.perf_counter()
        with server:
            futs = [
                server.submit(
                    rng.standard_normal((image_size, image_size, 3))
                    .astype(np.float32)
                )
                for _ in range(requests)
            ]
            resps = [f.result(timeout=600.0) for f in futs]
        wall = time.perf_counter() - t0
        stats = server.stats()
        statuses = sorted({r.status for r in resps})
        all_ok = all(r.status == "ok" for r in resps)
        rec = {
            "mode": "serve",
            "transport": transport,
            "wire_codec": cluster._codec_cfg.spec,
            "weight_cache": weight_cache,
            "requests": requests,
            "max_batch": max_batch,
            "deadline_s": deadline_s,
            "probe_s": [float(x) for x in probe],
            "statuses": statuses,
            "all_ok": all_ok,
            "retries": sum(r.retries for r in resps),
            "failures": list(cluster.failures),
            "wall_s": wall,
            "throughput_rps": requests / wall,
            "p50_ms": stats["p50_ms"],
            "p99_ms": stats["p99_ms"],
            "comm_mb": cluster.comm_bytes / 2 ** 20,
        }
        print(f"{requests} requests in {wall:.2f}s -> "
              f"{rec['throughput_rps']:.1f} req/s  "
              f"p50={stats['p50_ms']:.1f}ms p99={stats['p99_ms']:.1f}ms  "
              f"statuses={statuses} retries={rec['retries']}")
        return rec
    finally:
        cluster.shutdown()


def _clean_exit(code: int) -> None:
    """Flush and leave through ``os._exit``: the ROADMAP pre-existing
    hang — an ``xla`` slave completes its steps, prints results, then
    the interpreter never exits (XLA runtime threads vs CPython
    finalization) — cannot bite a process that skips finalization.
    Everything user-visible (stdout/stderr, --out JSONL) is already
    written and flushed by the time this runs, so nothing is lost."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slowdowns", default=None,
                    help="comma list; device 0 is the master (default "
                         "1.0,1.5,3.0 flat; with --groups GxM pass 1 + G*M "
                         "entries — root then group devices chunked M per "
                         "group — or just the root, group devices default "
                         "to 1.0)")
    ap.add_argument("--groups", default=None, metavar="GxM",
                    help="two-tier topology: G sub-master groups of M "
                         "devices each (e.g. 2x3); the root plans disjoint "
                         "batch rows across groups (exact dW all-reduce), "
                         "each group re-partitions its rows internally on "
                         "--group-partition.  With --transport tcp each "
                         "sub-master is a real OS process")
    ap.add_argument("--group-partition", default="auto",
                    choices=["kernel", "spatial", "batch", "auto"],
                    help="conv split axis INSIDE each group (the root's "
                         "inter-group axis is always batch)")
    ap.add_argument("--master-nic-mbps", type=float, default=None,
                    help="emulate ONE shared master port of this speed "
                         "serialized across all root links (inproc only) — "
                         "the master-ingress-bound regime where the "
                         "hierarchy beats a flat cluster")
    ap.add_argument("--backends", default=None,
                    help="comma list of conv backends per device "
                         "(numpy|xla|pallas|sim), default numpy everywhere; "
                         "in callback mode (no --train-pipeline) the master "
                         "(device 0) must stay numpy")
    ap.add_argument("--pipeline", action="store_true",
                    help="double-buffered microbatch scatter/gather")
    ap.add_argument("--train-pipeline", action="store_true",
                    help="pipeline the FULL training step (forward + "
                         "backward) with the activation-stashing "
                         "conv_train_step schedule; implies --pipeline and "
                         "allows any master backend (direct driver)")
    ap.add_argument("--partition", default="kernel",
                    choices=["kernel", "spatial", "batch", "auto"],
                    help="conv split axis: output channels (kernel, the "
                         "paper), height strips + halo exchange (spatial), "
                         "batch rows + replicated kernel + dW all-reduce "
                         "(batch), or per-layer predicted-wall-clock pick "
                         "(auto)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["fp32", "fp16", "bf16"],
                    help="compact wire codec at the socket boundary; "
                         "master-side accumulation stays float32")
    ap.add_argument("--wire-codec", default=None,
                    help="full compressor stack, superseding --wire-dtype: "
                         "one stage for everything ('fp16', 'int8') or "
                         "per message class, e.g. "
                         "'weights=fp16,acts=int8,grads=topk:0.05' "
                         "(top-k applies to gradients only, with "
                         "master-side error feedback)")
    ap.add_argument("--no-weight-cache", action="store_true",
                    help="disable the versioned weight-broadcast cache "
                         "(slaves then receive kernels every slab/"
                         "microbatch — the pre-cache wire, for A/B runs)")
    ap.add_argument("--bandwidth-mbps", type=float, default=None,
                    help="emulated master<->slave link speed (the paper's "
                         "~5 Mbps Wi-Fi); default: infinitely fast links. "
                         "With --transport tcp this only overrides the "
                         "measured planning bandwidth")
    ap.add_argument("--transport", default="inproc",
                    choices=["inproc", "tcp", "shm"],
                    help="the wire: in-process queue emulation (threads, "
                         "seed behaviour), real localhost TCP sockets "
                         "with one OS subprocess per slave, or shm — "
                         "subprocess slaves with bulk arrays on zero-copy "
                         "shared-memory rings (co-located only)")
    ap.add_argument("--expected-slaves", type=int, default=None,
                    help="wait for this many HAND-LAUNCHED slaves to "
                         "join the listener instead of spawning any "
                         "(implies --transport tcp; pass only the "
                         "master's --slowdowns entry and export "
                         "REPRO_CLUSTER_AUTH in both environments)")
    ap.add_argument("--listen-host", default="127.0.0.1",
                    help="TCP listener bind interface; 0.0.0.0 accepts "
                         "slaves from remote hosts")
    ap.add_argument("--listen-port", type=int, default=0,
                    help="TCP listener port (0 = kernel-assigned); fix "
                         "it so remote slaves know where to connect")
    ap.add_argument("--heartbeat-s", type=float, default=None,
                    help="slave liveness interval: spawned slaves beat "
                         "every this many seconds and the master "
                         "declares a silent link dead after 3x (tcp "
                         "only); hand-launched slaves must pass the "
                         "same --heartbeat-s themselves")
    ap.add_argument("--serve", action="store_true",
                    help="serve a stream of forward-pass requests through "
                         "the continuous-batching ClusterServer instead of "
                         "training (see docs/serving.md); exits nonzero "
                         "unless every request completes under deadline")
    ap.add_argument("--requests", type=int, default=20,
                    help="synthetic requests to serve with --serve")
    ap.add_argument("--deadline-s", type=float, default=30.0,
                    help="per-request deadline for --serve")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="dynamic-batching slot count for --serve")
    ap.add_argument("--image-size", type=int, default=16,
                    help="request image height/width for --serve")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--c1", type=int, default=8)
    ap.add_argument("--c2", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--out", default=None, help="append the record as JSONL")
    args = ap.parse_args()
    configure_compile_cache()

    # the flat default topology makes no sense under --groups: there the
    # default is "just the root", group devices filling in at 1.0
    slowdowns_s = args.slowdowns or ("1.0" if args.groups else "1.0,1.5,3.0")
    slowdowns = [float(s) for s in slowdowns_s.split(",")]
    backends = args.backends.split(",") if args.backends else None
    transport = args.transport
    if args.expected_slaves is not None:
        transport = "tcp"  # external joins only exist on the real wire
    try:
        if args.serve:
            rec = run_serve(
                slowdowns, backends,
                microbatches=args.microbatches, c1=args.c1, c2=args.c2,
                requests=args.requests, deadline_s=args.deadline_s,
                max_batch=args.max_batch, image_size=args.image_size,
                partition=args.partition, wire_dtype=args.wire_dtype,
                wire_codec=args.wire_codec,
                weight_cache=not args.no_weight_cache,
                bandwidth_mbps=args.bandwidth_mbps, transport=transport,
                expected_slaves=args.expected_slaves,
                listen_host=args.listen_host, listen_port=args.listen_port,
                heartbeat_s=args.heartbeat_s,
            )
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            _clean_exit(0 if rec["all_ok"] else 1)
        rec = run_hetero(
            slowdowns, backends, pipeline=args.pipeline,
            train_pipeline=args.train_pipeline,
            microbatches=args.microbatches, c1=args.c1, c2=args.c2,
            batch=args.batch, steps=args.steps,
            partition=args.partition, wire_dtype=args.wire_dtype,
            wire_codec=args.wire_codec,
            weight_cache=not args.no_weight_cache,
            bandwidth_mbps=args.bandwidth_mbps, transport=transport,
            expected_slaves=args.expected_slaves,
            listen_host=args.listen_host, listen_port=args.listen_port,
            heartbeat_s=args.heartbeat_s,
            groups=args.groups, group_partition=args.group_partition,
            master_nic_mbps=args.master_nic_mbps,
        )
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    except SystemExit:
        raise  # config validation: no cluster (and no xla threads) yet
    except BaseException:
        traceback.print_exc()
        _clean_exit(1)
    _clean_exit(0)


if __name__ == "__main__":
    main()
