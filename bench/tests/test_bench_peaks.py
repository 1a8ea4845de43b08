"""The table of peaks is keyed by ``device_kind``; an unknown kind is an
error, never a default."""
import pytest

from bench.peaks import peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "_source", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks(kind)
