"""The benchmark's own tests, run by path (``pytest chip_bench/tests``),
on the CPU: the checkout root and ``src`` go on the import path, and the
CPU backend is given four devices, so that a four-chip cell's mesh can
be rehearsed."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

FOUR = "--xla_force_host_platform_device_count=4"
if FOUR not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {FOUR}".strip()
