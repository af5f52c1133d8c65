"""Bytes the program copied between host and device, per step, in MB
(1e6 bytes): its counters ``h2d_bytes`` + ``d2h_bytes`` while the trace
collects (``spans.program_totals``) over the window's steps.  A count
of shapes: the same work reads the same.  Nothing to read where the
program keeps no counters."""
from chip_bench import spans


def read(m):
    program = spans.program_totals()
    if program is None:
        return None
    _totals, counters = program
    return (counters["h2d_bytes"] + counters["d2h_bytes"]) / 1e6 / m.steps
