import pytest

from lib.peaks import peaks
from lib.roofline import least_seconds, score_ops_bytes


def test_ops_and_bytes_of_the_full_pool_pass_by_hand():
    # a_pad = 131072 rows x n_cols = 114688 columns x (520 + 16) planes
    ops, nbytes = score_ops_bytes(131072, 114688, 520 + 16, 1024)
    pairs = 131072 * 114688
    assert pairs == 15_032_385_536
    assert ops == 2 * pairs * 536 == 16_114_717_294_592
    # operands once in bf16: (131072 + 114688) rows x 536 x 2 bytes
    # winners: one int32 per row and per 1024-column block (112 blocks)
    assert nbytes == 245_760 * 536 * 2 + 131072 * 112 * 4 == 322_174_976


def test_which_roof_bounds_it():
    peak = peaks("TPU v5 lite")
    ops, nbytes = score_ops_bytes(131072, 114688, 536, 1024)
    least, roof = least_seconds(ops, nbytes, peak)
    assert roof == "compute"
    assert least == pytest.approx(16_114_717_294_592 / 197e12)  # 81.8 ms
    assert 0.0817 < least < 0.0819
    # a thin pass (few rows against the whole pool) is memory-bound
    ops, nbytes = score_ops_bytes(8, 114688, 536, 1024)
    assert least_seconds(ops, nbytes, peak)[1] == "memory"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
