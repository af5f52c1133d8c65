"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``load_events`` reads the trace with ``jax.profiler.ProfileData`` into
plain tuples; ``summarize`` reduces them.  The traced window is the span
from the start of the first ``bench.train`` step annotation to the end
of the last; device time outside it is cut off.

- busy: the union of the intervals in which an operation ran on a
  device's op line;
- conv time: the summed durations of convolution events: ops whose
  name holds "conv" (XLA convolutions, the Pallas kernels
  ``conv2d_pallas``, ``conv2d_dx_pallas``, ``conv2d_dw_pallas``) and
  XLA output fusions (``kind=kOutput``: a convolution or a dot with its
  epilogue; in this model the dots are the fc layer's, a few tenths of
  a percent of the time);
- collective time: the summed durations of collective events (ops whose
  name holds ``all-gather``, ``all-reduce``, ``reduce-scatter``,
  ``collective-permute`` or ``all-to-all``, with their ``-start`` and
  ``-done`` halves and fusions), and the exposed part of it: the union
  of the collective intervals less the union of every other op's, the
  time a device spent in collectives with nothing else running on it;
- the breakdown: the device ops that took most time, and the longest
  idle gaps of the first device, each named by the innermost span open
  at the gap's middle on the host thread that ran the steps.

On a TPU an op event's name is its HLO instruction, ``%name = type
op(operands), kind=..., calls=...``; the short name before `` = `` and
the ``kind`` classify it.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Sequence, Tuple

STEP_SPAN = "bench.train"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"

Interval = Tuple[int, int]


def load_events(prof):
    """``(host, device)`` from a ``jax.profiler.ProfileData``: the spans
    ``[(name, start_ns, end_ns, depth)]`` of the host thread that ran the
    ``bench.train`` steps, and per device id the ops of its op line
    ``[(short_name, start_ns, end_ns, kind)]``."""
    host, device = [], {}
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                if line.name == OP_LINE:
                    device[int(m.group(1))] = [
                        (*classify(ev.name), int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
            elif plane.name.startswith("/host:") and not host:
                events = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                          for ev in line.events]
                if any(n == STEP_SPAN for n, _, _ in events):
                    host = nest(events)
    device = {d: [(n, s, e, k) for n, k, s, e in ops] for d, ops in device.items()}
    return host, device


def classify(name: str):
    """``(short_name, kind)`` of an op event named by its HLO text."""
    short, _, rest = name.partition(" = ")
    m = re.search(r"kind=(\w+)", rest)
    return short, m.group(1) if m else ""


def nest(events):
    """Spans of one thread with their nesting depth."""
    out, stack = [], []
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1] <= start:
            stack.pop()
        out.append((name, start, end, len(stack)))
        stack.append(end)
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in union(intervals))


def clip(ops, lo: int, hi: int):
    return [(n, max(s, lo), min(e, hi), c) for n, s, e, c in ops if e > lo and s < hi]


def is_conv(name: str, kind: str) -> bool:
    return "conv" in name.lower() or kind == "kOutput"


COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")


def is_collective(name: str) -> bool:
    return COLLECTIVE.search(name.lower()) is not None


def summarize(host, device: Dict[int, list], top: int = 10) -> dict:
    """The numbers of one traced window (see the module docstring);
    ``None`` when the trace holds no ``bench.train`` span."""
    steps = [(s, e) for n, s, e, _ in host if n == STEP_SPAN]
    if not steps:
        return None
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    window_ns = hi - lo
    per_device, op_time, collectives = {}, collections.Counter(), set()
    for dev, ops in sorted(device.items()):
        ops = clip(ops, lo, hi)
        spans = [(s, e) for _, s, e, _ in ops]
        coll = [(s, e) for n, s, e, _ in ops if is_collective(n)]
        other = [(s, e) for n, s, e, _ in ops if not is_collective(n)]
        busy = length(spans)
        per_device[dev] = {
            "busy_ns": busy,
            "conv_ns": sum(e - s for n, s, e, c in ops if is_conv(n, c)),
            "collective_ns": sum(e - s for s, e in coll),
            "exposed_collective_ns": busy - length(other),
        }
        for n, s, e, _ in ops:
            op_time[n] += e - s
        collectives.update(n for n, _, _, _ in ops if is_collective(n))
    gaps = []
    if device:
        first = min(device)
        busy = union([(s, e) for _, s, e, _ in clip(device[first], lo, hi)])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = sorted(((a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a),
                      key=lambda g: g[0] - g[1])[:top]
        gaps = [(host_label(host, (a + b) // 2), b - a) for a, b in gaps]
    return {
        "window_ns": window_ns,
        "steps": len(steps),
        "devices": per_device,
        "collectives": sorted(collectives),
        "device_ops": [[n, t / 1e9] for n, t in op_time.most_common(top)],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps],
    }


def host_label(host, t: int) -> str:
    """The innermost host span open at time ``t`` (``idle`` if none)."""
    best, depth = "idle", -1
    for n, s, e, d in host:
        if s <= t < e and d > depth:
            best, depth = n, d
    return best
