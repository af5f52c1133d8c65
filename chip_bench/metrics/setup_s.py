"""Process start to window start (host clock): imports, device start,
weights and batches from the seed, the system's own start-up, the
checked steps, warm-up and every compile or cache load."""


def read(m):
    return m.setup_s
