"""Training samples completed in the window over the window's seconds
(host clock, every step synced), all samples over all of that time."""


def read(m):
    return m.samples / m.window_s
