import pytest

from bench.lib.window import busy_by_chip, idle_share, rate, union_length


def test_rate_is_all_work_over_the_whole_window():
    assert rate(300.0, 12.0) == 25.0
    with pytest.raises(ValueError):
        rate(1.0, 0.0)


@pytest.mark.parametrize("intervals, lo, hi, want", [
    ([], 0, 10, 0),
    ([(1, 3), (2, 5)], 0, 10, 4),          # overlap counted once
    ([(1, 3), (4, 6)], 0, 10, 4),
    ([(-5, 2), (8, 20)], 0, 10, 4),        # clipped to the window
    ([(3, 4), (1, 2), (1.5, 3.5)], 0, 10, 3),
    ([(97, 99)], 5, 64, 0),                # wholly after the window
    ([(-9, -2), (1, 2)], 0, 10, 1),        # wholly before it
])
def test_union_length(intervals, lo, hi, want):
    assert union_length(intervals, lo, hi) == pytest.approx(want)


def test_chip_idle_share_from_job_records():
    jobs = [
        {"chips": [0], "start": 0.0, "end": 4.0},
        {"chips": [0, 1], "start": 5.0, "end": 10.0},
        {"chips": [2], "start": 2.0, "end": 3.0},
    ]
    busy = busy_by_chip(jobs)
    # chips 0..3 over 10 s: 9 + 5 + 1 + 0 busy chip-seconds of 40
    assert idle_share(busy, [0, 1, 2, 3], 0.0, 10.0) == pytest.approx(
        1 - 15 / 40)
    assert idle_share(busy, [3], 0.0, 10.0) == 1.0
