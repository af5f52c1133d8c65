"""Collective time not hidden behind other work, per step: for each chip
the union of its collective ops' intervals less the union of every other
op's in the traced window (``trace_reduce.summarize``), the worst chip's,
over the window's steps.  Nothing to read where no collective ran."""


def read(m):
    if m.trace is None:
        return None
    devs = list(m.trace["devices"].values())
    if not any(d["collective_ns"] > 0 for d in devs):
        return None
    return max(d["exposed_collective_ns"] for d in devs) / 1e6 / m.trace["steps"]
