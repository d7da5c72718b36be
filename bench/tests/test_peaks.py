"""The peak table: v5e's published numbers; an unknown chip raises."""
import pytest

from peaks import peaks


def test_v5e():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v9 imaginary")
