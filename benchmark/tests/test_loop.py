"""The window and its arithmetic, on a clock the test moves."""

import pytest

from lib import loop


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_actions_start_while_the_window_is_open_and_always_finish():
    clock = FakeClock()

    def action(i):
        clock.now += 4.0
        return i

    samples = loop.closed_loop(action, 10.0, clock)
    # starts at 0, 4 and 8 s; the third ends at 12 s, past the window
    assert [s.start_s for s in samples] == [0.0, 4.0, 8.0]
    assert samples[-1].end_s == 12.0
    assert [s.record for s in samples] == [0, 1, 2]


def test_at_least_one_action_finishes():
    clock = FakeClock()

    def action(i):
        clock.now += 30.0

    assert len(loop.closed_loop(action, 0.0, clock)) == 1
    assert len(loop.closed_loop(action, 10.0, clock)) == 1


def test_an_action_that_raises_is_a_sample_and_a_broken_cell_stops():
    clock = FakeClock()

    def action(i):
        clock.now += 1.0
        if i != 1:
            raise ValueError("boom")
        return "ok"

    samples = loop.closed_loop(action, 100.0, clock)
    assert [bool(s.error) for s in samples] == [True, False, True, True, True]
    assert "ValueError: boom" in samples[0].error


def test_median_and_nearest_rank():
    assert loop.median([3.0, 1.0, 2.0]) == 2.0
    assert loop.median([4.0, 1.0, 2.0, 3.0]) == 2.5
    ten = [float(i) for i in range(1, 11)]
    assert loop.nearest_rank(ten, 0.9) == 9.0
    assert loop.nearest_rank(ten[:9] + [100.0] * 2, 0.9) == 100.0  # 11 samples
    assert loop.nearest_rank([5.0], 0.9) == 5.0
    thirty = [1.0] * 27 + [2.0, 3.0, 4.0]
    assert loop.nearest_rank(thirty, 0.9) == 1.0
    assert loop.nearest_rank(thirty + [5.0], 0.9) == 2.0


def test_rate_is_over_all_the_work_and_all_the_time():
    samples = [loop.Sample(0.0, 4.0, None, ""), loop.Sample(4.0, 8.0, None, ""),
               loop.Sample(8.0, 12.5, None, "")]
    assert loop.rate(1000, samples) == pytest.approx(3000 / 12.5)
    assert loop.durations(samples) == [4.0, 4.0, 4.5]
