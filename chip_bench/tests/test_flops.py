"""Operation and byte counts against hand counts."""
import json
import os

import pytest

from chip_bench import flops
from chip_bench.archs import cnn

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_conv2_fwd_at_500_1500():
    # 2 * 128 * 16 * 16 * 5 * 5 * 500 * 1500
    f, b = flops.conv_work("fwd", 128, 16, 5, 500, 1500)
    assert f == 1.2288e12
    # x (128*16*16*500) + w (5*5*500*1500) + y (128*16*16*1500), fp32
    assert b == 4 * (16_384_000 + 18_750_000 + 49_152_000)


@pytest.mark.parametrize("name, per_sample", [
    # conv1 fwd+dW: 2 * 2*32*32*75*C1; conv2 fwd+dX+dW: 3 * 2*16*16*25*C1*C2;
    # fc fwd+dX+dW: 3 * 2*(8*8*C2)*10
    ("cifar_cnn_500_1500", 2 * 76_800_000 + 3 * 9_600_000_000 + 3 * 1_920_000),
    ("cifar_cnn_50_500", 2 * 7_680_000 + 3 * 320_000_000 + 3 * 640_000),
])
def test_model_flops_per_sample(name, per_sample):
    assert cnn.model_flops_per_sample(cfg(name)) == per_sample


def test_step_flops_at_batch_128():
    assert cnn.model_flops_per_sample(cfg("cifar_cnn_500_1500")) * 128 == pytest.approx(3.707e12, rel=1e-3)
    assert cnn.model_flops_per_sample(cfg("cifar_cnn_50_500")) * 128 == pytest.approx(0.125e12, rel=1e-2)


def test_shard_work_sums_to_whole():
    c = cfg("cifar_cnn_500_1500")
    whole = cnn.shard_work(c, 128, {"conv1": 500, "conv2": 1500})
    quarter = cnn.shard_work(c, 128, {"conv1": 125, "conv2": 375})
    assert 4 * quarter[0] == whole[0]
    per_sample = cnn.model_flops_per_sample(c) - 3 * 1_920_000
    assert whole[0] == per_sample * 128
    with_dx = cnn.shard_work(c, 128, {"conv1": 500, "conv2": 1500}, with_input_dx=True)
    assert with_dx[0] - whole[0] == 76_800_000 * 128
    # four microbatch calls read the weight shard four times
    four = cnn.shard_work(c, 128, {"conv2": 1500}, calls=4)
    one = cnn.shard_work(c, 128, {"conv2": 1500}, calls=1)
    assert four[0] == one[0] and four[1] - one[1] == 3 * 3 * 18_750_000 * 4
