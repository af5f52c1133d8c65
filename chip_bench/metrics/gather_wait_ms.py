"""Time the cluster's master was blocked on members' results, per step:
the change of ``cluster.timing.gather_wait_s`` over the window (a host
clock counter of the program) over the window's steps."""


def read(m):
    if "gather_wait_s" not in m.counters:
        return None
    return 1000.0 * m.counters["gather_wait_s"] / m.steps
