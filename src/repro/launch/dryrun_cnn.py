import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The paper's OWN architecture on the production mesh: the CIFAR-10 CNN
# with kernel-sharded convolutions (core/conv_shard.py), lowered and
# compiled at batch 1024 (the paper's largest), comparing the faithful
# gather schedule against the channel-sharded (beyond-paper) one.

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compat import mesh_context  # noqa: E402
from repro.configs.base import InputShape  # noqa: E402
from repro.configs.cifar_cnn import CONFIGS  # noqa: E402
from repro.core.conv_shard import make_sharded_train_step  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_name  # noqa: E402
from repro.models.cnn import init_cnn  # noqa: E402
from repro.models.registry import rules_for_mode  # noqa: E402
from repro.roofline.analysis import RooflineReport  # noqa: E402
from repro.roofline.hlo_parse import analyze_hlo  # noqa: E402


def dryrun_cnn(arch: str, batch: int, tp_mode: str, multi_pod: bool = False):
    cfg = CONFIGS[arch]
    mesh = make_production_mesh(multi_pod=multi_pod)
    jitted, _ = make_sharded_train_step(cfg, mesh, rules_for_mode(tp_mode), batch)
    abstract = jax.eval_shape(lambda: init_cnn(jax.random.key(0), cfg))
    with mesh_context(mesh):
        lowered = jitted.lower(
            abstract,
            jax.ShapeDtypeStruct((batch, 32, 32, 3), jnp.float32),
            jax.ShapeDtypeStruct((batch,), jnp.int32),
        )
        compiled = lowered.compile()
    chips = mesh.devices.size
    hc = analyze_hlo(compiled.as_text(), num_partitions=chips)
    mem = compiled.memory_analysis()
    hbm = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    rec = {
        "arch_id": arch, "shape": f"train_b{batch}", "mesh": mesh_name(mesh),
        "tp_mode": tp_mode, "chips": chips,
        "compute_s": hc.flops / 197e12,
        "memory_s": hc.memory_bytes / 819e9,
        "collective_s": hc.collective_bytes / 50e9,
        "collective_breakdown": hc.by_kind,
        "hbm_bytes_per_device": int(hbm),
    }
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: rec[k])
    print(
        f"{arch:22s} b={batch:5d} {tp_mode:9s} "
        f"C={rec['compute_s']:.2e} M={rec['memory_s']:.2e} "
        f"X={rec['collective_s']:.2e} dom={dom.split('_')[0]:10s} "
        f"hbm/dev={hbm/2**20:8.1f}MiB", flush=True,
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    recs = []
    for arch in CONFIGS:
        for mode in ("gather", "megatron"):
            recs.append(dryrun_cnn(arch, args.batch, mode))
    if args.out:
        with open(args.out, "a") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
