"""The least bytes and operations of a product, against hand counts."""
import pytest

from bench.work import least_bytes, least_flops, least_seconds

# a 4 x 4 matrix with 6 stored entries:
#   [a . b .]
#   [. c . .]
#   [d . e f]
#   [. . . .]
N, NNZ = 4, 6


def test_bytes_of_one_vector():
    # values 6*4 + column indices 6*4 + row pointer 5*4 + x 4*4 + y 4*4
    assert least_bytes(N, N, NNZ, k=1) == 24 + 24 + 20 + 16 + 16


def test_bytes_of_a_panel_count_each_vector_once():
    # the matrix once, x and y once per right-hand side
    assert least_bytes(N, N, NNZ, k=3) == 24 + 24 + 20 + 3 * (16 + 16)


def test_operations():
    assert least_flops(NNZ, k=1) == 12
    assert least_flops(NNZ, k=3) == 36


def test_least_time_is_the_larger_bound():
    pk = {"hbm_bytes_per_s": 100.0, "flops_per_s": 1.0}
    assert least_seconds(N, N, NNZ, 1, pk) == pytest.approx(12.0)
    pk = {"hbm_bytes_per_s": 1.0, "flops_per_s": 1e9}
    assert least_seconds(N, N, NNZ, 1, pk) == pytest.approx(100.0)


def test_unknown_device_kind_is_an_error():
    from bench.peaks import peaks
    with pytest.raises(KeyError):
        peaks("TPU v99")
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
