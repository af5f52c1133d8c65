"""The whole step's share of the chips' peak: model operations per
sample (the architecture's ``model_flops_per_sample``) times the samples
per second of the traced window, over chips times the peak of
``peaks.json``."""


def read(m):
    rate = m.samples / m.window_s
    peak = m.peaks()["flops_per_s"] * m.cell.chips
    return 100.0 * m.cell.arch.model_flops_per_sample(m.cell.cfg) * rate / peak
