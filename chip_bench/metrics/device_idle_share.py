"""1 - (union of op intervals on a device's op line) / traced window,
averaged over the chips."""


def read(m):
    if m.trace is None or not m.trace["devices"]:
        return None
    devs = list(m.trace["devices"].values())
    busy = sum(d["busy_ns"] for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / m.trace["window_ns"])
