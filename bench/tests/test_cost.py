"""The kernels' operation and byte counts against hand counts."""
import pytest

from harness import cells, peaks


@pytest.mark.parametrize("args, flops, nbytes", [
    # K=2 partitions of 512 rows, d=8: 2*512*512 entries * (2*8 + 6);
    # 2*2 diagonal tiles of 256*256, and 2*512 rows * (8 + 9) values, 4 B
    ((2, 512, 8, 256), 524_288 * 22, (4 * 65_536 + 1024 * 17) * 4),
    # K=1, m=300 (two 256-row tiles), d=3
    ((1, 300, 3, 256), 90_000 * 12, (2 * 65_536 + 300 * 12) * 4),
])
def test_fused_cd_pass(args, flops, nbytes):
    assert cells.load_module("cost", "fused_cd_pass").cost(*args) == \
        (flops, nbytes)


@pytest.mark.parametrize("args, flops, nbytes", [
    ((512, 18), 512 * 120, (512 * 18 + 1024 + 72) * 4),
    ((8, 2), 8 * 24, (16 + 16 + 8) * 4),
])
def test_odm_svrg_grad(args, flops, nbytes):
    assert cells.load_module("cost", "odm_svrg_grad").cost(*args) == \
        (flops, nbytes)


def test_least_time_names_its_bound():
    peak = peaks.of("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert peaks.least_time(197e12, 1.0, peak) == (1.0, "compute")
    assert peaks.least_time(1.0, 819e9, peak) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.of("TPU v9 imaginary")
