"""The fixed work counts and the peaks, against values worked by hand."""

import pytest

import peaks
import work


def test_qr_flops_by_hand():
    # 2 k^2 (max(m, n) - k/3): 2048^2 -> 2 * 2048^2 * (2048 - 2048/3)
    assert work.qr_flops(2048, 2048) == pytest.approx(
        2 * 4194304 * (2048 - 682.6666666666666))
    assert work.qr_flops(2048, 2048) == pytest.approx(1.1453246122666666e10)
    # tall: k = n = 128, max = 8192 -> 2 * 16384 * (8192 - 42.666...)
    assert work.qr_flops(8192, 128) == pytest.approx(2.6703735466666666e8)
    # wide counts like its transpose
    assert work.qr_flops(128, 8192) == work.qr_flops(8192, 128)


def test_qr_work_square_and_tall():
    f, b = work.qr_work(2048, 2048)
    assert f == pytest.approx(2.2906492245333332e10)   # GEQRF + ORGQR
    assert b == 4 * (2 * 2048 * 2048 + 2048 * 2048)     # A, Q, R once
    f, b = work.qr_work(512, 256)
    assert f == 2 * work.qr_flops(512, 256)
    assert b == 4 * (512 * 256 + 512 * 256 + 256 * 256)
    assert work.total_work([(512, 256), (2048, 2048)]) == pytest.approx(
        (f + 2.2906492245333332e10, b + 4 * 3 * 2048 * 2048))


def test_least_time_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    t, bound = work.least_time(*work.qr_work(2048, 2048), pk)
    assert bound == "compute"
    assert t == pytest.approx(2.2906492245333332e10 / 197e12)
    t, bound = work.least_time(1.0, 819e9, pk)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_unknown_device_kind_is_an_error():
    assert peaks.PEAKS["TPU v5 lite"].flops == 197e12
    assert peaks.PEAKS["TPU v5 lite"].hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
