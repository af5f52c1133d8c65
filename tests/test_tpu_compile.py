"""The conv kernels compile for a TPU v5e chip at the paper's widest
widths (cifar_cnn_500_1500: C1=500, C2=1500, 5x5), without a chip.

The TPU compiler is installed with jax and compiles for a described
topology; it refuses what interpret-mode tests cannot see, such as a
block that overflows VMEM (conv2's dX did, before the contracted channel
axis was tiled).  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library.  Batch 32
is one microbatch of ``chip_smoke.py``'s batch-128 step; the kernels
grid over the batch, so VMEM per step does not depend on it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv2d import conv2d_dw_pallas, conv2d_dx_pallas, conv2d_pallas

B, K = 32, 5
# (layer, image size, Cin, Cout) — conv2's Cout=750 is a half
# kernel-axis shard, the share one of two equal members receives
LAYERS = {
    "conv1": (32, 3, 500),
    "conv2": (16, 500, 1500),
    "conv2_shard750": (16, 500, 750),
}
CASES = [
    ("conv1", "fwd"), ("conv1", "dx"), ("conv1", "dw"),
    ("conv2", "fwd"), ("conv2", "dx"), ("conv2", "dw"),
    ("conv2_shard750", "dx"),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with JAX's persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("layer,kind", CASES, ids=[f"{l}-{k}" for l, k in CASES])
def test_conv_kernel_compiles_for_v5e(one_chip, layer, kind):
    hw, cin, cout = LAYERS[layer]

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    x, g, w = spec(B, hw, hw, cin), spec(B, hw, hw, cout), spec(K, K, cin, cout)
    if kind == "fwd":
        lowered = conv2d_pallas.lower(x, w)
    elif kind == "dx":
        lowered = conv2d_dx_pallas.lower(g, w)
    else:
        lowered = conv2d_dw_pallas.lower(x, g, K, K)
    compiled = lowered.compile()  # raises what the chip's compiler refuses
    assert "tpu_custom_call" in compiled.as_text()
