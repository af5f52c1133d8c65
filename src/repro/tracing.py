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

``to_device`` / ``to_host`` are ``jax.device_put`` / ``jax.device_get``
under the spans ``host.to_device`` / ``host.to_host``, and count the
bytes of every real crossing (``counters``): a numpy array going up, a
``jax.Array`` coming down.  An array already on the side asked for
passes unchanged, with no span.  Both copies are explicit transfers, so
under ``jax.transfer_guard("disallow")`` a copy made anywhere else
raises.  ``to_host`` first waits for the device under ``device.wait``,
so ``host.to_host`` times the copy alone; ``ready`` waits the same way
for a result that stays on the device.  ``count_assembly`` counts the
gathers the cluster assembled on each side (``device_assembles``,
``host_assembles``).

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
    count]``; per direction: bytes; per side: assembled gathers."""

    def __init__(self):
        self.spans: Dict[Tuple[str, str], List[float]] = {}
        self.counts = {"h2d_bytes": 0, "d2h_bytes": 0,
                       "device_assembles": 0, "host_assembles": 0}


_lock = threading.Lock()
_process = _Totals()
_traced = _Totals()
_local = threading.local()  # .stack: the spans open on this thread


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, or None before jax is imported."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    return getattr(profiler, "TraceAnnotation", None)


def _tracing() -> bool:
    """Whether a profiler trace is being collected."""
    cls = _annotation_class()
    return cls is not None and cls.is_enabled()


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
        self.traced = _tracing()
        self._children = 0.0
        _open_spans().append(self)
        # the host-clock interval sits just inside the annotation's
        self._annotation = _annotation_class()(self.name) if self.traced else None
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


def _count(key: str, n: int, traced: bool) -> None:
    with _lock:
        for totals in (_process, _traced) if traced else (_process,):
            totals.counts[key] += int(n)


def on_device(a) -> bool:
    """Whether ``a`` is a ``jax.Array`` (False before jax is imported)."""
    jax = sys.modules.get("jax")
    return jax is not None and isinstance(a, jax.Array)


def to_device(a, dtype=None):
    """``a`` on JAX's default device: a numpy input goes up through
    ``jax.device_put`` under ``host.to_device`` and adds its bytes to
    ``h2d_bytes``; a ``jax.Array`` stays where it is (cast on the device
    where ``dtype`` asks).  The span times the host's part of the
    upload: the transfer may end later."""
    import jax

    if isinstance(a, jax.Array):
        return a if dtype is None or a.dtype == dtype else a.astype(dtype)
    with span("host.to_device") as s:
        out = jax.device_put(np.asarray(a, dtype))
    _count("h2d_bytes", out.nbytes, s.traced)
    return out


def to_host(a, dtype=None) -> np.ndarray:
    """``a`` as a numpy array: a ``jax.Array`` is waited for under
    ``device.wait``, comes down through ``jax.device_get`` under
    ``host.to_host`` and adds its bytes to ``d2h_bytes``; anything else
    is ``np.asarray(a, dtype)`` on the host."""
    if not on_device(a):
        return np.asarray(a, dtype)
    import jax

    with span("device.wait"):
        a.block_until_ready()
    with span("host.to_host") as s:
        out = np.asarray(jax.device_get(a), dtype)
    _count("d2h_bytes", a.nbytes, s.traced)
    return out


def ready(out):
    """``out`` (a ``jax.Array`` or a tuple of them) once the device has
    computed it, waited for under ``device.wait``; it stays there."""
    import jax

    with span("device.wait"):
        jax.block_until_ready(out)
    return out


def count_assembly(on_device_side: bool) -> None:
    """Count one gather assembled on the device (``device_assembles``)
    or on the host (``host_assembles``)."""
    key = "device_assembles" if on_device_side else "host_assembles"
    _count(key, 1, _tracing())


def counters(traced: bool = False) -> Dict[str, int]:
    """Bytes copied host to device (``h2d_bytes``) and back
    (``d2h_bytes``) by ``to_device``/``to_host``, and the gathers
    assembled on each side (``device_assembles``, ``host_assembles``),
    in this process, or with ``traced`` only while a profiler trace was
    being collected."""
    with _lock:
        return dict((_traced if traced else _process).counts)


def span_totals(traced: bool = False) -> Dict[Tuple[str, str], Tuple[float, float, int]]:
    """``{(span name, thread name): (seconds, self seconds, count)}`` of
    the spans closed in this process, or with ``traced`` of those opened
    while a profiler trace was being collected.  Self seconds leave out
    the time of the spans nested in it on its thread."""
    with _lock:
        return {k: tuple(v) for k, v in (_traced if traced else _process).spans.items()}
