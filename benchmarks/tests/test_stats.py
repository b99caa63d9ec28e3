import pytest

from benchmarks import stats


def test_percentile_interpolates_between_order_statistics():
    xs = list(range(1, 11))  # 1..10
    assert stats.percentile(xs, 0) == 1
    assert stats.percentile(xs, 100) == 10
    assert stats.percentile(xs, 50) == pytest.approx(5.5)
    assert stats.percentile(xs, 90) == pytest.approx(9.1)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_token_gaps_one_sample_per_token_after_the_first_frame():
    # first frame at 1.0 s (its gap is the TTFT, not a token gap); then a
    # frame of one token 50 ms later and a frame of two tokens 80 ms later
    frames = [(1.0, 1), (1.05, 1), (1.13, 2)]
    gaps = stats.token_gaps_ms(frames)
    assert gaps == pytest.approx([50.0, 40.0, 40.0])
    assert stats.token_gaps_ms([(1.0, 3)]) == []


def test_spread_is_interquartile_range_over_median():
    import statistics

    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)


def test_unknown_device_kind_is_an_error():
    assert stats.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        stats.load_peaks("TPU v9 imaginary")
