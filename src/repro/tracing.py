"""Named host spans on the profiler's clock, and counters of the bytes
copied between host and device.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` while a profiler
trace is being collected, so the trace shows the span on the thread that
ran it, on the clock of the device ops.  Given a ``LayerTiming`` and one
of its fields, it also adds its host-clock seconds to that field: the
field and the span measure one interval.  The program keeps totals per
span name and thread (``span_totals``) over the whole process, and apart
over the time a profiler trace was being collected, so that a traced
window can be read without parsing the trace file.

``to_device`` / ``to_host`` are ``jnp.asarray`` / ``np.asarray`` under
the spans ``host.to_device`` / ``host.to_host``, and count the bytes of
every real crossing (``counters``): a numpy array going up, a
``jax.Array`` coming down.  ``to_host`` first waits for the device under
``device.wait``, so ``host.to_host`` times the copy alone.

The totals are per process, like the profiler they mirror.  jax is
imported only by a copy: a process that has not imported jax (a host-CPU
cluster member) opens spans on the host clock alone, as no profiler can
be collecting there.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List, Tuple

import numpy as np


class _Totals:
    """Per ``(span name, thread name)``: ``[seconds, self seconds,
    count]``; per direction: bytes."""

    def __init__(self):
        self.spans: Dict[Tuple[str, str], List[float]] = {}
        self.bytes = {"h2d_bytes": 0, "d2h_bytes": 0}


_lock = threading.Lock()
_process = _Totals()
_traced = _Totals()
_local = threading.local()  # .stack: the spans open on this thread


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, or None before jax is imported."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    return getattr(profiler, "TraceAnnotation", None)


def _open_spans() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """One named span (see the module docstring), as a context manager
    that gives itself: ``start`` and ``end`` are its edges on the host
    clock (``time.perf_counter``), ``end`` once it has closed."""

    __slots__ = ("name", "timing", "field", "start", "end", "traced",
                 "_children", "_annotation")

    def __init__(self, name: str, timing=None, field: str = None):
        self.name, self.timing, self.field = name, timing, field
        self.start = self.end = None

    def __enter__(self) -> "span":
        cls = _annotation_class()
        self.traced = cls is not None and cls.is_enabled()
        self._children = 0.0
        _open_spans().append(self)
        # the host-clock interval sits just inside the annotation's
        self._annotation = cls(self.name) if self.traced else None
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        elapsed = self.end - self.start
        if self.timing is not None:
            setattr(self.timing, self.field, getattr(self.timing, self.field) + elapsed)
        stack = _open_spans()
        stack.pop()
        if stack:
            stack[-1]._children += elapsed
        key = (self.name, threading.current_thread().name)
        with _lock:
            for totals in (_process, _traced) if self.traced else (_process,):
                t = totals.spans.setdefault(key, [0.0, 0.0, 0])
                t[0] += elapsed
                t[1] += elapsed - self._children
                t[2] += 1
        return False


def _count(direction: str, nbytes: int, traced: bool) -> None:
    with _lock:
        for totals in (_process, _traced) if traced else (_process,):
            totals.bytes[direction] += int(nbytes)


def to_device(a, dtype=None):
    """``jnp.asarray(a, dtype)`` under ``host.to_device``; a numpy input
    adds the bytes put on the device to ``h2d_bytes``.  The span times
    the host's part of the upload: the transfer may end later."""
    import jax.numpy as jnp

    with span("host.to_device") as s:
        out = jnp.asarray(a, dtype)
    if isinstance(a, np.ndarray):
        _count("h2d_bytes", out.nbytes, s.traced)
    return out


def to_host(a, dtype=None) -> np.ndarray:
    """``np.asarray(a, dtype)`` under ``host.to_host``; a ``jax.Array``
    is first waited for under ``device.wait``, and adds its bytes to
    ``d2h_bytes``."""
    jax = sys.modules.get("jax")
    crossing = jax is not None and isinstance(a, jax.Array)
    if crossing:
        with span("device.wait"):
            a.block_until_ready()
    with span("host.to_host") as s:
        out = np.asarray(a, dtype)
    if crossing:
        _count("d2h_bytes", a.nbytes, s.traced)
    return out


def counters(traced: bool = False) -> Dict[str, int]:
    """Bytes copied host to device (``h2d_bytes``) and back
    (``d2h_bytes``) by ``to_device``/``to_host`` in this process, or
    with ``traced`` only while a profiler trace was being collected."""
    with _lock:
        return dict((_traced if traced else _process).bytes)


def span_totals(traced: bool = False) -> Dict[Tuple[str, str], Tuple[float, float, int]]:
    """``{(span name, thread name): (seconds, self seconds, count)}`` of
    the spans closed in this process, or with ``traced`` of those opened
    while a profiler trace was being collected.  Self seconds leave out
    the time of the spans nested in it on its thread."""
    with _lock:
        return {k: tuple(v) for k, v in (_traced if traced else _process).spans.items()}
