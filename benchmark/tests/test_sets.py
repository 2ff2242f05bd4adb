"""tools/sets.py reckons a set's spread as the driver does (ledger, PR 28:
"A spread leaves out the run farthest from its median where that narrows
it ... the mean of the two spreads may be at most 50% of the bound"), on
hand-made sets."""

import importlib.util
import os

import pytest

from conftest import BENCH

spec = importlib.util.spec_from_file_location(
    "bench_tools_sets", os.path.join(BENCH, "tools", "sets.py"))
sets = importlib.util.module_from_spec(spec)
spec.loader.exec_module(sets)


@pytest.mark.parametrize("values, want", [
    # the farthest run is the top one: it goes, the rest range over 4
    ([100.0, 101.0, 102.0, 103.0, 104.0, 120.0], 4 / 102.5),
    # the farthest is the bottom one
    ([80.0, 100.0, 101.0, 102.0, 103.0, 104.0], 4 / 101.5),
    # no run stands out: one end goes all the same (it never widens)
    ([100.0, 101.0, 102.0, 103.0, 104.0, 105.0], 4 / 102.5),
    ([5.0, 5.0, 5.0], 0.0),
    # two runs: nothing to leave out
    ([100.0, 110.0], 10 / 105.0),
    ([7.0], 0.0),
], ids=["top_out", "bottom_out", "even", "equal", "two", "one"])
def test_spread_leaves_out_the_farthest_run(values, want):
    assert sets.spread(values) == pytest.approx(want)
    assert sets.spread(values[::-1]) == pytest.approx(want)


def test_the_shape_of_pr28s_sets():
    """Two sets of six `query_s` whose spreads are what the driver read in
    its check of PR 28 (0.00658 and 0.00753 s; its bound, 5% of 0.268334 s,
    was 0.0134167 s): four runs close together, one low, one high. A
    quartile distance that never looks at the end runs (numpy's) passes
    them at any bound; the driver's reckoning refuses 5%; this tool asks
    for three times the widest, which holds them."""
    import statistics

    set_a = [0.26500, 0.27100, 0.27120, 0.27140, 0.27158, 0.27800]
    set_b = [0.26387, 0.27097, 0.27110, 0.27125, 0.27140, 0.27900]
    bound = 0.0134167 / 0.268334
    s = sets.summary("query_s", [set_a, set_b])
    medians = [statistics.median(set_a), statistics.median(set_b)]
    assert s["medians"] == pytest.approx(medians)
    assert [x * m for x, m in zip(s["spreads"], medians)] \
        == pytest.approx([0.00658, 0.00753], abs=1e-7)
    for v in (set_a, set_b):
        q1, _, q3 = statistics.quantiles(v, n=4, method="inclusive")
        assert 5 * (q3 - q1) / statistics.median(v) < 0.01 < bound
    assert s["mean"] > bound / 2                # the driver: too noisy
    assert s["widest"] == max(s["spreads"])
    assert s["three_times_widest"] == pytest.approx(3 * s["widest"])
    assert s["three_times_widest"] > bound
    assert s["mean"] <= s["three_times_widest"] / 3
    # statistics.quantiles' own (exclusive) quartiles do see the end runs,
    # and still read about half of what the driver reads here
    assert max(s["iqrs"]) < 0.62 * s["widest"]
    assert s["eight_times_widest_iqr"] == pytest.approx(8 * max(s["iqrs"]))


def test_iqr_is_statistics_quantiles():
    # six runs 0..5: the quartiles by the exclusive method are 0.75 and 4.25
    assert sets.iqr([10.0, 11.0, 12.0, 13.0, 14.0, 15.0]) \
        == pytest.approx(3.5 / 12.5)


def test_iqr_less_the_farthest_run():
    # 0..4 and a run far off: the five left have quartiles 0.5 and 3.5
    values = [10.0, 11.0, 12.0, 13.0, 14.0, 30.0]
    assert sets.less_farthest(values) == [10.0, 11.0, 12.0, 13.0, 14.0]
    assert sets.iqr(values, True) == pytest.approx(3.0 / 12.5)
    assert sets.iqr(values, True) <= sets.spread(values)
    assert sets.less_farthest([3.0, 1.0]) == [1.0, 3.0]
    s = sets.summary("m", [values, values[::-1]])
    assert s["iqrs_less_farthest"] == pytest.approx([3.0 / 12.5] * 2)


def test_window_line_is_found_among_the_earlier_lines():
    lines = ['{"cell": "q6_scan"}',
             '{"phases_s": {}, "rate": 2.0, "rate_less_longest": 3.0, '
             '"action_s": [0.1, 0.5]}',
             '{"correct": true}']
    assert sets.window_line(lines)["rate_less_longest"] == 3.0
    assert sets.window_line(lines[:1]) == {}


def test_a_run_that_is_not_correct_is_left_out_and_the_sets_go_on(
        monkeypatch, tmp_path, capsys):
    """Every run a canned process: the second run of the first set counts
    a speculative task. The tool prints it, leaves it out of its set,
    makes the rest of both sets and ends with exit code 1."""
    import json
    from types import SimpleNamespace

    made = []

    def fake_run(cmd, cwd, stdout, stderr):
        made.append(cmd)
        seed = int(cmd[cmd.index("--seed") + 1])
        bad = len(made) == 2
        value = 0.07 + 0.001 * (seed % 7) + 0.0001 * len(made)
        stdout.write(json.dumps({"phases_s": {}, "rate": 2.0,
                                 "rate_less_longest": 2.5,
                                 "action_s": [0.07, 3.4 if bad else 0.2]})
                     + "\n")
        stdout.write(json.dumps({
            "correct": not bad, "attempted": 500, "failed": int(bad),
            "metrics": {"query_s": {"value": value, "unit": "s"}},
            "device": {}, "compared": {"q6.speculativeTasks": [2 * bad, 0]}})
            + "\n")
        return SimpleNamespace(returncode=0)

    monkeypatch.setattr(sets.subprocess, "run", fake_run)
    rc = sets.main(["--workload", "q6_scan", "--out", str(tmp_path),
                    "--sets", "2", "--runs", "3", "--seed0", "5"])
    assert rc == 1 and len(made) == 6
    # the same seeds in both sets, each run with the cell's run_seconds
    seeds = [c[c.index("--seed") + 1] for c in made]
    assert seeds[:3] == seeds[3:] and len(set(seeds)) == 3
    assert all(c[c.index("--seconds") + 1] == "45" for c in made)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    runs = [line for line in lines if "set" in line]
    assert [r["correct"] for r in runs] == [True, False, True, True, True, True]
    assert runs[1]["longest_action_s"] == 3.4
    assert runs[1]["compared"]["q6.speculativeTasks"] == [2, 0]
    (summary,) = [line for line in lines if "metric" in line]
    assert summary["metric"] == "query_s" and len(summary["spreads"]) == 2
    values = [[r["metrics"]["query_s"] for r in runs
               if r["set"] == s and r["correct"]] for s in (0, 1)]
    assert [len(v) for v in values] == [2, 3]
    assert summary["spreads"] == pytest.approx([sets.spread(v) for v in values])
