"""``chip_smoke.py`` off the chip: it refuses to run without a TPU (and
without the repo beside it), and its phases — run here at tiny widths,
with Pallas interpreted by name and four forced host devices — pass
their own checks, so a chip run only meets the chip's own faults."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *args], env=env, cwd=cwd, capture_output=True,
        text=True, timeout=timeout,
    )


def _has_result_line(stdout: str) -> bool:
    return any(line.startswith('{"ok"') for line in stdout.splitlines())


def test_exits_nonzero_without_a_tpu(tmp_path):
    out = _run([SMOKE], cwd=str(tmp_path))
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not _has_result_line(out.stdout)


def test_exits_nonzero_alone(tmp_path):
    """Copied away from ``src/``, the script cannot import the program
    and must not print a result."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert out.returncode != 0
    assert not _has_result_line(out.stdout)


REHEARSAL = textwrap.dedent(
    """
    import jax
    import chip_smoke as cs

    jax.config.update("jax_default_matmul_precision", "highest")
    cs.kernel_precision_phase(batch=2, hw=8, cin=24, cout=16, interpret=True)
    members = ["xla", "pallas:interpret", "numpy"]
    rec = cs.train_phase(backends=members, c1=8, c2=16, batch=8, steps=2)
    serve = cs.serve_phase(backends=members, c1=8, c2=16, requests=4)
    cs.mesh_phase(jax.devices()[:4], c1=8, c2=16, batch=8)
    print(json.dumps({"losses": rec["losses"], "statuses": serve["statuses"]}))
    """
)


def test_phases_pass_their_checks_at_tiny_widths(tmp_path):
    out = _run(
        ["-c", "import json\n" + REHEARSAL], cwd=ROOT, timeout=600,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert out.returncode == 0, out.stderr[-4000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(rec["losses"]) == 2
    assert rec["statuses"] == ["ok"]
