"""The paper's kernel split as GSPMD shardings: the CNN train step on a
(1, 4) mesh spreads each conv kernel over four devices and equals the
single-device step.  Runs in a subprocess so the 4-device XLA flag never
leaks into the main test process."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.compat import mesh_context
    from repro.core.conv_shard import make_sharded_train_step
    from repro.launch.hetero import train_inputs
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import cnn_loss, make_cnn_config
    from repro.models.registry import rules_for_mode

    cfg = make_cnn_config(8, 12)
    params, images, labels = train_inputs(cfg, 4)
    mesh = make_mesh((1, 4), ("data", "model"))
    step, (psh, ish, lsh) = make_sharded_train_step(
        cfg, mesh, rules_for_mode("gather"), 4, lr=0.05)
    with mesh_context(mesh):
        new, loss, _ = step(jax.device_put(params, psh),
                            jax.device_put(images, ish),
                            jax.device_put(labels, lsh))
    for name, cout in (("conv1", 8), ("conv2", 12)):
        k = new[name]["kernel"]
        assert {s.device.id for s in k.addressable_shards} == {0, 1, 2, 3}
        assert {s.data.shape[-1] for s in k.addressable_shards} == {cout // 4}

    (ref_loss, _), grads = jax.value_and_grad(
        lambda p: cnn_loss(p, images, labels, cfg=cfg), has_aux=True)(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for name in ("conv1", "conv2"):
        np.testing.assert_allclose(
            np.asarray(new[name]["kernel"]),
            np.asarray(params[name]["kernel"] - 0.05 * grads[name]["kernel"]),
            rtol=1e-4, atol=1e-6)
    print("OK")
    """
)


def test_sharded_train_step_splits_kernels_and_matches_one_device():
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.join(ROOT, "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("OK")
