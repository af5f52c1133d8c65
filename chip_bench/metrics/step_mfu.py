"""The whole step's share of the chips' peak: model operations per
sample (``flops.model_flops_per_sample``) times the samples per second
of the traced window, over chips times the peak of ``peaks.json``."""
from chip_bench.flops import model_flops_per_sample


def read(m):
    rate = m.samples / m.window_s
    peak = m.peaks()["flops_per_s"] * m.cell.chips
    return 100.0 * model_flops_per_sample(m.cell.cfg) * rate / peak
