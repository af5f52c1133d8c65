"""The program's own spans: read from a profiler trace, or from the
program's totals.

A trace (``.xplane.pb``) holds every span the program opened while it
was collected (``repro.tracing``), one line per host thread.  ``load``
reads them with the device ops; ``reduce`` keeps the spans of the
window (the extent of the ``bench.train`` steps), clipped to it, and
gives per span name and thread the total time, the self time (less the
part its child program spans on the same thread cover) and the count,
and device 0's idle time split by the innermost span open on the step
thread (the thread that ran the ``bench.train`` steps): a program span
where one is open, else the benchmark's own (``bench.step``,
``bench.sync``, ``bench.train``), else ``outside``.

The program also keeps totals of its spans and of the bytes it copied
between host and device, over the time a trace was being collected;
``program_totals`` gives them to the metric readers, and None where the
program keeps none.

    python3 chip_bench/spans.py <trace.xplane.pb[.gz]>

prints the reduction of a trace file as JSON.
"""
from __future__ import annotations

import collections
import gzip
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

PREFIXES = ("cluster.", "cnn.", "member.", "host.", "device.")
BENCH_PREFIX = "bench."
STEP_SPAN = "bench.train"
OUTSIDE = "outside"

Span = Tuple[str, int, int, str]  # name, start_ns, end_ns, thread


def is_program(name: str) -> bool:
    return name.startswith(PREFIXES)


def load(prof):
    """``(spans, device)`` from a ``jax.profiler.ProfileData``: the
    program's and the benchmark's spans of every host thread, the thread
    named by its line's name and position, and per device id its ops
    ``[(short_name, start_ns, end_ns, kind)]`` as ``trace_reduce``
    reads them."""
    from chip_bench import trace_reduce

    spans: List[Span] = []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}:{i}"
            spans += [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), thread)
                      for ev in line.events
                      if is_program(ev.name) or ev.name.startswith(BENCH_PREFIX)]
    return spans, trace_reduce.load_events(prof)[1]


def load_file(path: str):
    """``load`` of a trace file, gzipped or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return load(ProfileData.from_serialized_xspace(f.read()))
    return load(ProfileData.from_file(path))


def _nested(spans: Sequence[Span]):
    """Per thread, its spans sorted outermost first, each with the index
    of its parent (the innermost earlier span that contains it) or -1."""
    by_thread = collections.defaultdict(list)
    for sp in spans:
        by_thread[sp[3]].append(sp)
    out = {}
    for thread, sps in by_thread.items():
        sps = sorted(sps, key=lambda s: (s[1], -s[2]))
        parents, stack = [], []
        for i, (_, start, end, _) in enumerate(sps):
            while stack and sps[stack[-1]][2] <= start:
                stack.pop()
            parents.append(stack[-1] if stack else -1)
            stack.append(i)
        out[thread] = (sps, parents)
    return out


def reduce(spans: Sequence[Span], device: Optional[Dict[int, list]] = None) -> Optional[dict]:
    """The program's spans of one traced window (see the module
    docstring); None when the trace holds no ``bench.train`` step.

    ``{"window_ns", "steps", "step_thread", "spans": {name: {thread:
    {"total_ns", "self_ns", "count"}}}, "idle_ns": {label: ns}}``;
    ``idle_ns`` is empty without a device 0."""
    steps = [sp for sp in spans if sp[0] == STEP_SPAN]
    if not steps:
        return None
    lo, hi = min(s[1] for s in steps), max(s[2] for s in steps)
    step_thread = steps[0][3]
    clipped = [(n, max(s, lo), min(e, hi), t) for n, s, e, t in spans if e > lo and s < hi]

    table: Dict[str, Dict[str, dict]] = {}
    program = [sp for sp in clipped if is_program(sp[0])]
    for thread, (sps, parents) in _nested(program).items():
        child_ns = [0] * len(sps)
        for i, p in enumerate(parents):
            if p >= 0:
                child_ns[p] += sps[i][2] - sps[i][1]
        for (name, s, e, _), c in zip(sps, child_ns):
            row = table.setdefault(name, {}).setdefault(
                thread, {"total_ns": 0, "self_ns": 0, "count": 0})
            row["total_ns"] += e - s
            row["self_ns"] += e - s - c
            row["count"] += 1

    idle: Dict[str, int] = {}
    if device and 0 in device:
        idle = idle_split([sp for sp in clipped if sp[3] == step_thread],
                          [(s, e) for _, s, e, _ in device[0]], lo, hi)
    return {"window_ns": hi - lo, "steps": len(steps), "step_thread": step_thread,
            "spans": table, "idle_ns": idle}


def idle_split(thread_spans: Sequence[Span], busy: Sequence[Tuple[int, int]],
               lo: int, hi: int) -> Dict[str, int]:
    """The time in ``[lo, hi)`` that no ``busy`` interval covers, split
    by the label of the innermost span of ``thread_spans`` open then (a
    program span's name, else the benchmark span's, else ``outside``)."""
    from chip_bench import trace_reduce

    busy = trace_reduce.union([(max(s, lo), min(e, hi)) for s, e in busy])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    sps = sorted(thread_spans, key=lambda s: (s[1], -s[2]))
    cuts = sorted({x for _, s, e, _ in sps for x in (s, e)} | {x for g in gaps for x in g})
    out: Dict[str, int] = collections.Counter()
    stack: List[Span] = []  # open spans, outermost first
    nxt, g = 0, 0
    for a, b in zip(cuts, cuts[1:]):
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps):
            break
        while stack and stack[-1][2] <= a:
            stack.pop()
        while nxt < len(sps) and sps[nxt][1] <= a:
            while stack and stack[-1][2] <= sps[nxt][1]:
                stack.pop()
            if sps[nxt][2] > a:
                stack.append(sps[nxt])
            nxt += 1
        if gaps[g][0] <= a:  # [a, b) lies inside the gap: the cuts hold its edges
            out[label(stack)] += b - a
    return dict(out)


def label(stack: Sequence[Span]) -> str:
    """The innermost program span of ``stack``, else its innermost
    span, else ``outside``."""
    for name, *_ in reversed(stack):
        if is_program(name):
            return name
    return stack[-1][0] if stack else OUTSIDE


def program_totals():
    """``(span_totals, counters)`` the program kept while a profiler
    trace was being collected (``repro.tracing``), or None where the
    program keeps none."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing.span_totals(traced=True), tracing.counters(traced=True)


def self_seconds(totals: dict, names: Sequence[str]) -> float:
    """Summed self seconds of the spans ``names``, every thread."""
    return sum(self_s for (name, _), (_, self_s, _) in totals.items() if name in names)


def main(argv=None) -> int:
    (path,) = argv if argv is not None else sys.argv[1:]
    summary = reduce(*load_file(path))
    print(json.dumps(summary, indent=1, sort_keys=True))
    return 0 if summary is not None else 1


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    sys.exit(main())
