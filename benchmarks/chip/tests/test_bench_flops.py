"""The operation and byte counts behind every roofline share, and the
table of peaks."""
import pytest

from bench import common, flops


def test_matmul_counts():
    f, b = flops.matmul(2048, 14336, 5120, 2)
    assert f == 2 * 2048 * 14336 * 5120
    assert b == 2 * (2048 * 5120 + 5120 * 14336 + 2048 * 14336)


def test_flash_counts_are_causal():
    f, b = flops.flash_causal(1, 32, 8, 2048, 128, 2)
    full = 4 * 32 * 2048 * 2048 * 128          # q·k and p·v, every pair
    assert f == full / 2
    assert b == 2 * (2 * 32 * 2048 * 128 + 2 * 8 * 2048 * 128)


def test_roofline_share_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(100.0, 1.0, 2.0, peak) == (50.0, "compute")
    assert flops.roofline_share(1.0, 100.0, 20.0, peak) == (50.0, "memory")


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        common.peak_for("TPU v99 imaginary")
    assert common.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
