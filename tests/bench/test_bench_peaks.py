"""The table of peaks knows the v5e and refuses any other chip."""
import pytest

from bench import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks(kind)
