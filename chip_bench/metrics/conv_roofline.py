"""Least time of the conv work the chips executed in the traced window
over the summed device time of the conv events in its trace.  The work
is counted from shapes (the architecture's ``shard_work``) for each
chip's kernel widths; least time is the larger of operations over peak
and bytes over HBM bandwidth.  Nothing to read without conv events."""


def read(m):
    if m.trace is None:
        return None
    conv_s = sum(d["conv_ns"] for d in m.trace["devices"].values()) / 1e9
    if conv_s <= 0:
        return None
    p = m.peaks()
    least = sum(max(f / p["flops_per_s"], b / p["hbm_bytes_per_s"])
                for f, b in m.shard_work.values())
    return 100.0 * least / conv_s
