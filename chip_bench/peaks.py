"""The chip's published peaks, from ``peaks.json``, keyed by JAX's
``device_kind``.  A kind not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """``{"flops_per_s", "hbm_bytes_per_s", ...}`` of one chip."""
    with open(TABLE) as f:
        devices = json.load(f)["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {TABLE}; "
                       f"known: {sorted(devices)}")
    return devices[device_kind]
