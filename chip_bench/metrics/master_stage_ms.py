"""Host time of the master-only stages of the cluster train step, per
step: the self time (copies and device waits left out) of the program's
spans ``cluster.stage_fwd``, ``cluster.stage_bwd``, ``cluster.head`` and
``cnn.update`` while the trace collects (``spans.program_totals``),
over the window's steps.  Nothing to read where the program keeps no
totals."""
from chip_bench import spans

STAGES = ("cluster.stage_fwd", "cluster.stage_bwd", "cluster.head", "cnn.update")


def read(m):
    program = spans.program_totals()
    if program is None:
        return None
    totals, _bytes = program
    return 1000.0 * spans.self_seconds(totals, STAGES) / m.steps
