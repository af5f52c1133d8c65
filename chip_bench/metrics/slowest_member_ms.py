"""The slowest conv member's time per step: for each member thread the
summed time of its ``member.*`` spans (the ops it computed, with their
emulated slowdown), for the master its ``cluster.master_conv`` spans,
while the trace collects (``spans.program_totals``); the largest over
the window's steps.  Nothing to read where the program keeps no totals
or ran no conv."""
from chip_bench import spans


def read(m):
    program = spans.program_totals()
    if program is None:
        return None
    totals, _bytes = program
    per_member = {}
    for (name, thread), (total_s, _self_s, _count) in totals.items():
        if name.startswith("member."):
            per_member[thread] = per_member.get(thread, 0.0) + total_s
        elif name == "cluster.master_conv":
            per_member["master"] = per_member.get("master", 0.0) + total_s
    if not per_member:
        return None
    return 1000.0 * max(per_member.values()) / m.steps
