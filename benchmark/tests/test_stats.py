"""The benchmark's arithmetic."""
import pytest

from benchmark.harness import stats


def test_segment_median_is_deaf_to_one_slow_segment_and_the_total_is_not():
    # eleven segments of equal work at 944 items/s, one of them 1% slow:
    # the median (the per-layer trainer.step_ms) reads the undisturbed
    # rate; the total over the window (items_s_chip) has to move
    work, rate = 16 * 128, 944.0
    durations = [work / rate] * 11
    durations[4] *= 1.01
    stamps = [0.0]
    for d in durations:
        stamps.append(stamps[-1] + d)
    rates = stats.segment_rates(stamps, [work] * 11)
    assert stats.median(rates) == pytest.approx(rate, rel=1e-12)
    total = 11 * work / (stamps[-1] - stamps[0])
    assert total < rate * (1 - 8e-4)


def test_iqr_share_is_pythons_quartiles():
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
