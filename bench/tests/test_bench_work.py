"""Work counted from shapes, and the table of peaks."""
import pytest

from bench import work


def test_4096_16_shots_block_is_66_fields():
    assert work.block_fields(16) == 66
    assert work.block_bytes(4096, 4096, 16) == 66 * 4096 * 4096 * 4


def test_four_stripes_take_a_quarter_of_the_columns_each():
    whole = work.block_bytes(4096, 4096, 16)
    parts = work.stripe_block_bytes(4096, 4096, 16, 4)
    assert parts == [66 * 4096 * 1024 * 4] * 4
    assert sum(parts) == whole


def test_stripes_must_divide_the_width():
    with pytest.raises(ValueError):
        work.stripe_block_bytes(600, 600, 4, 7)


def test_operations_per_byte():
    assert work.block_ops(4096, 4096, 16, 4) == 17 * 16 * 4096 ** 2 * 4
    assert work.ops_per_byte(16, 4) == pytest.approx(17 * 16 * 4 / 264)


def test_v5e_peaks():
    p = work.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["flops_bf16"] == 197e12
    assert p["hbm_bytes"] == 16 * 1024 ** 3


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")
