"""Host time of the copies between host and device, per step: the self
time of the program's ``host.to_device`` and ``host.to_host`` spans on
every thread, as the program totals them while the trace collects
(``spans.program_totals``), over the window's steps.  Nothing to read
where the program keeps no totals."""
from chip_bench import spans


def read(m):
    program = spans.program_totals()
    if program is None:
        return None
    totals, _bytes = program
    return 1000.0 * spans.self_seconds(totals, ("host.to_device", "host.to_host")) / m.steps
