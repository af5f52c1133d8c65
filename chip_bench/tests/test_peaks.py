"""The peaks table: a known chip, and an unknown kind is an error."""
import pytest

from chip_bench.peaks import peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks_for(kind)
