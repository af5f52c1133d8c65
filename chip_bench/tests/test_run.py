"""The harness on the CPU: it refuses to give a result without a chip or
without the system under test; it drives both path modules at tiny
widths through a window and the comparison; it finds a new cell and a
new metric by name; and the comparison fails the control and every
planted fault."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chip_bench import control, run

ROOT = run.ROOT
SEED = 2 ** 40 + 12345  # wider than 32 bits, as the seeds a run is given may be


def tiny(cell_name, trace=False, root=ROOT, c1=4, c2=8, batch=8, traffic=None):
    """The cell at tiny widths, with Pallas members in interpret mode;
    ``traffic`` names another traffic file to drive it with."""
    cell = run.Cell.load(cell_name, trace, root)
    cell.cfg = dict(cell.cfg, c1_kernels=c1, c2_kernels=c2)
    if traffic is not None:
        cell.traffic = run.load_json(root, "chip_bench", "traffic", f"{traffic}.json")
    cell.traffic = dict(cell.traffic, batch=batch)
    if "members" in cell.traffic:
        cell.traffic["members"] = [
            "pallas:interpret" if m == "pallas" else m for m in cell.traffic["members"]]
    return cell


def drive(cell, path_cls, seconds=0.5, trace=False):
    import jax

    jax.config.update("jax_default_matmul_precision", cell.cfg["matmul_precision"])
    devices = jax.devices()[:cell.chips]
    meas, checks, failed, peak = run.run_cell(cell, path_cls, devices, SEED, seconds, trace)
    return meas, checks, failed


def correct(cell, checks):
    return all(v <= cell.limits[k] for k, v in checks.items())


def run_script(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "cnn500_cluster_chip", "--seed", str(SEED), "--seconds", "1",
        "--trace", "0"]


def test_no_tpu_no_result():
    r = run_script(ARGS, ROOT)
    assert r.returncode != 0
    assert "metrics" not in r.stdout and r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chip_bench"), tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    r = run_script(ARGS, tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


@pytest.mark.parametrize("cell_name, traffic", [
    ("cnn500_mesh_1chip", None),
    ("cnn500_mesh_4chip", None),  # on the four CPU devices of conftest.py
    ("cnn50_cluster_hetero", None),
    ("cnn500_cluster_chip", None),
])
def test_rehearsal(cell_name, traffic):
    cell = tiny(cell_name, traffic=traffic)
    meas, checks, failed = drive(cell, cell.path_class())
    assert failed == 0 and meas.steps >= 1
    assert correct(cell, checks), checks
    assert meas.compiles == 0, "nothing compiles inside the window"
    if cell.traffic["path"] == "cluster":
        assert meas.counters["gather_wait_s"] >= 0.0
    assert len(meas.shard_work) == cell.chips
    assert all(f > 0 for f, _ in meas.shard_work.values())


def test_traced_rehearsal_reads_no_device_metric_on_cpu():
    cell = tiny("cnn500_mesh_1chip", trace=True)
    meas, checks, failed = drive(cell, cell.path_class(), trace=True)
    assert meas.trace is not None and meas.trace["steps"] == meas.steps
    assert meas.trace["devices"] == {}  # the CPU has no device op line
    with pytest.raises(KeyError):  # and no peaks: a CPU number is never a device metric
        run.result_line(cell, meas, checks, failed, 0, [FakeDevice("cpu")], True)


class FakeDevice:
    platform = "cpu"
    id = 0

    def __init__(self, kind):
        self.device_kind = kind


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    """A later PR adds a cell and a metric as new files and new entries."""
    shutil.copytree(os.path.join(ROOT, "chip_bench"), tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = tmp_path / "chip_bench"
    shutil.copy(here / "traffic" / "mesh_1chip.json", here / "traffic" / "dummy_traffic.json")
    shutil.copy(here / "limits" / "cnn500_cluster_chip.json", here / "limits" / "dummy_cell.json")
    (here / "metrics" / "dummy_steps.py").write_text("def read(m):\n    return m.steps\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "dummy_cell", "config": "cifar_cnn_50_500",
                               "traffic": "dummy_traffic", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "dummy_steps", "unit": "count", "better": "higher",
                                "bound": 0.01, "source": "host_clock",
                                "workloads": ["dummy_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = tiny("dummy_cell", root=str(tmp_path))
    assert cell.traffic["path"] == "mesh"
    assert [m["name"] for m in cell.metrics] == ["samples_per_s", "setup_s", "dummy_steps"]
    meas, checks, failed = drive(cell, cell.path_class())
    line = run.result_line(cell, meas, checks, failed, 0, [FakeDevice("cpu")], False)
    assert line["metrics"]["dummy_steps"]["value"] == meas.steps
    assert list(line)[-1] == "checks"
    # cells that do not list it do not report it
    other = tiny("cnn500_cluster_chip", root=str(tmp_path))
    assert "dummy_steps" not in [m["name"] for m in other.metrics]


TOY_ARCH = '''"""A dense network of two hidden layers whose parameter tree is
three levels deep: its inputs, plain reference step and work counts."""
import math

from chip_bench.data import seed_key
from chip_bench.flops import fc_work
from chip_bench.reference import precision_of

LAYERS = (("body", "dense1"), ("body", "dense2"), ("head", "out"))


def widths(cfg):
    h = cfg["hidden"]
    return [(cfg["inputs"], h), (h, h), (h, cfg["classes"])]


def make_inputs(cfg, batch, ring, seed):
    import jax
    import jax.numpy as jnp

    def gen(key):
        kp, kx, ky = jax.random.split(key, 3)
        params = {}
        for (group, name), (n_in, n_out), k in zip(LAYERS, widths(cfg), jax.random.split(kp, 3)):
            params.setdefault(group, {})[name] = {
                "kernel": jax.random.normal(k, (n_in, n_out)) / math.sqrt(n_in),
                "bias": jnp.zeros((n_out,))}
        x = jax.random.normal(kx, (ring, batch, cfg["inputs"]))
        y = jax.random.randint(ky, (ring, batch), 0, cfg["classes"])
        return params, x, y

    return jax.block_until_ready(jax.jit(gen)(seed_key(seed)))


def reference_step(cfg, lr, precision="highest"):
    import jax
    import jax.numpy as jnp

    prec = precision_of(precision)

    def loss_fn(params, x, y):
        for group, name in LAYERS[:-1]:
            layer = params[group][name]
            x = jax.nn.relu(jnp.dot(x, layer["kernel"], precision=prec) + layer["bias"])
        out = params["head"]["out"]
        logp = jax.nn.log_softmax(jnp.dot(x, out["kernel"], precision=prec) + out["bias"])
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss

    return jax.jit(step)


def model_flops_per_sample(cfg):
    return sum(3 * fc_work("fwd", 1, n_in, n_out)[0] for n_in, n_out in widths(cfg))


def shard_work(cfg, batch, widths_, calls=1, with_input_dx=False):
    flops = nbytes = 0.0
    for n_in, n_out in widths(cfg):
        for op in ("fwd", "dx", "dw"):
            f, b = fc_work(op, batch, n_in, n_out)
            flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
'''

TOY_PATH = '''"""The toy network's system under test: a jitted SGD step of its own."""


class Path:
    with_input_dx = False

    def __init__(self, cfg, traffic, devices):
        import jax
        import jax.numpy as jnp

        lr, self.device = traffic["lr"], devices[0]

        def loss(p, x, y):
            for name in ("dense1", "dense2"):
                x = jax.nn.relu(x @ p["body"][name]["kernel"] + p["body"][name]["bias"])
            logits = x @ p["head"]["out"]["kernel"] + p["head"]["out"]["bias"]
            return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(y.shape[0]), y])

        def step(p, x, y):
            value, grads = jax.value_and_grad(loss)(p, x, y)
            return jax.tree.map(lambda a, g: a - lr * g, p, grads), value

        self._step = jax.jit(step)

    def place(self, params, images, labels):
        return params, [(images[i], labels[i]) for i in range(images.shape[0])]

    def step(self, params, x, y):
        return self._step(params, x, y)

    def step_conv_widths(self):
        return [(self.device.id, {}, 1)]

    def counters(self):
        return {}

    def close(self):
        pass
'''


def test_new_architecture_is_new_files_only(tmp_path):
    """A later PR adds an architecture, its configuration, a path, a
    traffic mix and limits as new files, and entries in BENCHMARK.json:
    the harness finds each by name, the run is correct, and a planted
    fault (half of the batch left out) is not."""
    shutil.copytree(os.path.join(ROOT, "chip_bench"), tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = tmp_path / "chip_bench"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}

    def new_file(rel, text):
        with open(here / rel, "x") as f:  # "x": never an edit of a file that is there
            f.write(text)

    new_file("archs/toy.py", TOY_ARCH)
    new_file("paths/toy.py", TOY_PATH)
    new_file("configs/toy_mlp.json", json.dumps(
        {"name": "toy_mlp", "arch": "toy", "inputs": 24, "hidden": 32, "classes": 5,
         "dtype": "float32", "matmul_precision": "highest"}))
    new_file("traffic/toy_traffic.json", json.dumps(
        {"path": "toy", "batch": 16, "ring": 4, "lr": 0.1, "checked_steps": 3,
         "warmup_steps_max": 3}))
    new_file("limits/toy_cell.json", json.dumps(
        {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-4}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy_mlp", "source": "a test",
                             "file": "chip_bench/configs/toy_mlp.json", "reduced": [],
                             "why": "a test"})
    bench["workloads"].append({"name": "toy_cell", "config": "toy_mlp",
                               "traffic": "toy_traffic", "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.Cell.load("toy_cell", False, str(tmp_path))
    assert cell.arch.__file__ == str(here / "archs" / "toy.py")
    meas, checks, failed = drive(cell, cell.path_class())
    assert failed == 0 and meas.steps >= 1
    assert correct(cell, checks), checks
    assert meas.shard_work[0][0] == cell.arch.model_flops_per_sample(cell.cfg) * 16 * meas.steps

    toy_path = cell.path_class()

    class HalfBatch(toy_path):
        def step(self, params, x, y):
            half = x.shape[0] // 2
            return super().step(params, x[:half], y[:half])

    meas, checks, failed = drive(cell, HalfBatch, seconds=0.2)
    assert not correct(cell, checks), checks
    assert all(p.read_bytes() == data for p, data in before.items())


@pytest.mark.parametrize("cell_name, kind, members, size", [
    ("cnn500_cluster_chip", "unchanged", 2, (16, 64, 16)),
    ("cnn500_cluster_chip", "half_batch", 2, (16, 64, 16)),
    ("cnn500_cluster_chip", "no_exchange", 2, (16, 64, 16)),
    ("cnn50_cluster_hetero", "unchanged", 3, (16, 64, 16)),
    ("cnn50_cluster_hetero", "half_batch", 3, (16, 64, 16)),
    ("cnn50_cluster_hetero", "no_exchange", 3, (16, 64, 16)),
    ("cnn500_mesh_1chip", "unchanged", 1, (16, 64, 16)),
    ("cnn500_mesh_1chip", "half_batch", 1, (16, 64, 16)),
    ("cnn500_mesh_4chip", "unchanged", 4, (16, 64, 16)),
    ("cnn500_mesh_4chip", "half_batch", 4, (16, 64, 16)),
    ("cnn500_mesh_4chip", "no_exchange", 4, (16, 64, 16)),
    # the control (three bf16 passes) at cifar_cnn_50_500's widths, under the
    # limits of the cell whose lower reading is 0 (its step is bit-identical
    # to the reference on the chip); the cluster cells' limits sit above
    # summation-order gaps that this written-out control reaches on the CPU
    ("cnn500_mesh_1chip", "control", 1, (50, 500, 16)),
    ("cnn500_mesh_4chip", "control", 4, (50, 500, 16)),
])
def test_broken_step_is_not_correct(cell_name, kind, members, size):
    """The harness's run, with the timed path broken underneath: the
    comparison reads the run as not correct under the cell's limits."""
    c1, c2, batch = size
    cell = tiny(cell_name, c1=c1, c2=c2, batch=batch)
    cell.chips = 1

    def path_cls(cfg, traffic, devices):
        return control.ReferencePath(cfg, traffic, devices, kind, members)

    meas, checks, failed = drive(cell, path_cls, seconds=0.2)
    assert not correct(cell, checks), checks


def test_reference_in_the_programs_place_is_correct():
    cell = tiny("cnn500_cluster_chip", c1=16, c2=64, batch=16)

    def path_cls(cfg, traffic, devices):
        return control.ReferencePath(cfg, traffic, devices, "reference")

    meas, checks, failed = drive(cell, path_cls, seconds=0.2)
    assert checks == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}
