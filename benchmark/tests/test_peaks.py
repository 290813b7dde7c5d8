import pytest

from harness.peaks import UnknownDevice, peaks_for


def test_v5e_row_is_the_published_one():
    row = peaks_for("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes"] == 16e9


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
