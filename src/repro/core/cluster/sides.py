"""Which side of the host<->device boundary the cluster's arrays live on.

An array the cluster holds is either a ``jax.Array`` on JAX's default
device or a numpy array on the host.  Members that compute on the chip
(``xla``, ``pallas``) return device arrays; host members (``numpy``)
return numpy.  The helpers here work on an array where it lives: a cut,
a cast, a concatenation or a sum of device arrays runs on the device,
and whatever has to cross goes through ``repro.tracing.to_device`` /
``to_host``, so that every copy is counted.  Cuts of device arrays use
static bounds (``lax.slice_in_dim``), so no index crosses either.

Import-light on purpose (numpy only): jax is imported only once a
device array is in hand, so host-CPU slave processes never load it.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro.tracing import on_device, to_device, to_host

__all__ = ["on_device", "cut", "float32", "to_side", "concat", "total"]


def cut(a, lo: int, hi: int, axis: int = 0):
    """``a[lo:hi]`` along ``axis``, on the side ``a`` lives on."""
    if on_device(a):
        from jax import lax

        return lax.slice_in_dim(a, int(lo), int(hi), axis=axis)
    index = [slice(None)] * a.ndim
    index[axis] = slice(lo, hi)
    return a[tuple(index)]


def float32(a):
    """``a`` as float32, on the side it lives on."""
    if on_device(a):
        return a if a.dtype == np.float32 else a.astype(np.float32)
    return np.asarray(a, np.float32)


def to_side(a, device: bool):
    """``a`` on the device (``device``) or on the host, crossing through
    ``repro.tracing`` only where it lives on the other side."""
    return to_device(a) if device else to_host(a)


def _side(parts: Sequence, device: Optional[bool]) -> bool:
    return any(on_device(p) for p in parts) if device is None else device


def concat(parts: Sequence, axis: int, device: Optional[bool] = None):
    """Concatenate ``parts`` on the device (``device``; by default where
    any part is already there, the others going up) or on the host."""
    if _side(parts, device):
        import jax.numpy as jnp

        return jnp.concatenate([to_device(p) for p in parts], axis=axis)
    return np.concatenate([to_host(p) for p in parts], axis=axis)


def total(parts: Sequence, device: Optional[bool] = None):
    """The sum of ``parts``, left to right, on the side ``concat`` would
    pick: the same float32 additions in the same order on either side."""
    move = to_device if _side(parts, device) else to_host
    out = move(parts[0])
    for p in parts[1:]:
        out = out + move(p)
    return out
