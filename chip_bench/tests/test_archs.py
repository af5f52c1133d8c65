"""The CNN's architecture module (``archs/cnn.py``) at a tiny size: the
same seed gives the same weights, batches and reference losses as the
harness gave before they moved there (the constants were read from the
harness's ``data.make_inputs`` and ``reference.reference_step`` of that
time), and a parameter tree of any depth flattens to dotted keys."""
import json
import os

import numpy as np
import pytest

from chip_bench import reference, run

SEED = 2 ** 40 + 12345
PINNED = {  # leaf: (sum, sum of squares), in float64
    "conv1.bias": (0.0, 0.0),
    "conv1.kernel": (-1.9555647189372394, 4.175922520922709),
    "conv2.bias": (0.0, 0.0),
    "conv2.kernel": (-1.8039709572603897, 7.854230029253837),
    "fc.bias": (0.0, 0.0),
    "fc.kernel": (-2.6657071877507406, 9.969246483295088),
    "images": (17.091488935020607, 49220.937981683106),
}
PINNED_LABEL_SUM = 56
PINNED_LOSSES = [2.173769950866699, 2.436455249786377, 2.168757200241089]


@pytest.fixture(scope="module")
def tiny_cnn():
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    with open(os.path.join(run.HERE, "configs", "cifar_cnn_500_1500.json")) as f:
        cfg = dict(json.load(f), c1_kernels=4, c2_kernels=8)
    arch = run.load_module("archs", cfg["arch"])
    return arch, cfg, arch.make_inputs(cfg, 8, 2, SEED)


def sums(a):
    a = np.asarray(a, np.float64)
    return float(a.sum()), float((a * a).sum())


def test_make_inputs_pinned(tiny_cnn):
    _arch, _cfg, (params, images, labels) = tiny_cnn
    got = {k: sums(v) for k, v in reference.to_host(params).items()}
    got["images"] = sums(images)
    assert sorted(got) == sorted(PINNED)
    for k, want in PINNED.items():
        assert got[k] == pytest.approx(want, rel=1e-6, abs=0.0), k
    assert images.shape == (2, 8, 32, 32, 3) and labels.shape == (2, 8)
    assert int(np.asarray(labels).sum()) == PINNED_LABEL_SUM


def test_reference_losses_pinned(tiny_cnn):
    arch, cfg, (params, images, labels) = tiny_cnn
    ring = np.array([0, 1, 0])  # three steps over the ring of two
    _states, losses = reference.run_reference(
        arch.reference_step(cfg, 0.002), params, images[ring], labels[ring])
    assert losses == pytest.approx(PINNED_LOSSES, rel=1e-6)


def test_to_host_flattens_any_depth():
    tree = {"a": {"b": {"c": np.ones(2, np.float32)}, "d": np.zeros(3)}, "e": np.float32(2)}
    flat = reference.to_host(tree)
    assert sorted(flat) == ["a.b.c", "a.d", "e"]
    assert all(v.dtype == np.float64 for v in flat.values())
    assert flat["e"] == 2.0 and flat["a.b.c"].tolist() == [1.0, 1.0]
