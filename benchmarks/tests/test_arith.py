import pytest

from benchmarks import arith


def test_a_rate_over_a_window_that_holds_a_stall_falls():
    steady = arith.rate(1000, 10.0)
    # the same work, and two seconds in which nothing completed
    assert arith.rate(1000, 12.0) < steady
    assert arith.rate(1000, 12.0) == pytest.approx(1000 / 12.0)


def test_a_rate_over_no_time_is_an_error():
    with pytest.raises(ValueError):
        arith.rate(1000, 0.0)
