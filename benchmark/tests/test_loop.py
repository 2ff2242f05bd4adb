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


def samples_of(durations, errors=()):
    """A closed loop's samples: each action starts where the last ended."""
    out, t = [], 0.0
    for i, d in enumerate(durations):
        out.append(loop.Sample(t, t + d, None, "boom" if i in errors else ""))
        t += d
    return out


@pytest.mark.parametrize("durations, errors, want", [
    # one sample: the plain rate
    ([4.0], (), 1000 / 4.0),
    # two: the shorter one alone
    ([4.0, 1.0], (), 1000 / 1.0),
    # a window of 70 ms actions with one that stood still for 5 s: the
    # rate of the others, as if it had not been there
    ([0.07] * 300 + [5.0] + [0.07] * 271, (), 1000 / 0.07),
    # no action stands out: the longest of equals goes, the rate stays
    ([0.5] * 90, (), 1000 / 0.5),
    # a failed action is an action of the window, as `rate` counts it,
    # and may be the longest
    ([0.07] * 10 + [2.0] + [0.07] * 10, (10,), 1000 / 0.07),
    ([0.07] * 10 + [2.0] + [0.07] * 10, (3,), 1000 / 0.07),
], ids=["n1", "n2", "one_stalled_action", "all_alike", "the_failed_one_is_longest",
        "another_failed"])
def test_rate_less_longest(durations, errors, want):
    samples = samples_of(durations, errors)
    assert loop.rate_less_longest(1000, samples) == pytest.approx(want)
    # the end-to-end rate is over all the work and all the time, stall included
    assert loop.rate(1000, samples) == pytest.approx(
        1000 * len(durations) / sum(durations))


def test_what_one_stalled_action_takes_from_each_rate():
    """The write cell's shape, 74 actions of 0.6 s: the two formulas part
    by nothing where the actions are alike, and the plain rate alone
    loses what a stall took."""
    even = samples_of([0.6] * 74)
    assert loop.rate_less_longest(6e6, even) == pytest.approx(loop.rate(6e6, even))
    stalled = samples_of([0.6] * 40 + [3.6] + [0.6] * 28)
    assert loop.rate(6e6, stalled) == pytest.approx(6e6 * 69 / 44.4)
    assert loop.rate_less_longest(6e6, stalled) == pytest.approx(1e7)
