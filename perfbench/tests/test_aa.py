"""The arithmetic of perfbench/tools/aa.py over hand-made runs: the two
measures of a spread, the rounding of a bound, a check's three verdicts, and
the table over result lines as `runs` writes them. No JAX, no chip."""
import json
import statistics

import pytest

from perfbench.tools import aa


def test_the_drivers_spread_leaves_out_the_farthest_run():
    v = [60.0, 61.0, 62.0, 63.0, 64.0, 90.0]
    assert aa.driver_spread(v) == 4.0                  # 90 is left out
    assert aa.driver_spread([3.0, 1.0]) == 2.0         # two runs: the range
    # the farthest below the median goes as well as one above it
    assert aa.driver_spread([10.0, 50.0, 51.0, 52.0]) == 2.0


def test_the_quartiles_spread_is_the_contracts():
    v = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    q = statistics.quantiles(v, n=4)
    assert aa.quartile_spread(v) == q[2] - q[0] == 3.5
    assert aa.quartile_spread([7.0]) == 0.0


@pytest.mark.parametrize("x, up", [(0.0301, 0.035), (0.03, 0.03),
                                   (0.0049, 0.005), (0.0, 0.0)])
def test_a_bound_is_rounded_up_to_the_next_step(x, up):
    assert aa.round_up(x, 0.005) == pytest.approx(up)


@pytest.mark.parametrize("name, a, b, bound, told", [
    # a tail, lower is better: the same runs on both sides
    ("ttft_p95_ms", [60, 61, 62, 61, 60, 62], [61, 60, 62, 61, 62, 60], 0.05,
     "unchanged"),
    # worse at the median by more than the bound
    ("ttft_p95_ms", [60, 61, 62, 61, 60, 62], [70, 71, 72, 71, 70, 72], 0.05,
     "regressed"),
    # not worse at the median, one side spreads wider than the bound
    ("ttft_p95_ms", [60, 61, 62, 61, 60, 62], [55, 58, 61, 64, 67, 61], 0.05,
     "unresolved"),
    # wide, and every run of one side better than every run of the other
    ("ttft_p95_ms", [60, 64, 68, 61, 66, 62], [50, 54, 58, 51, 56, 52], 0.05,
     "unchanged"),
    # a rate, higher is better: lower by more than the bound
    ("serve_tokens_per_s", [1500, 1510, 1505], [1400, 1410, 1405], 0.02,
     "regressed"),
    ("serve_tokens_per_s", [1500, 1510, 1505], [1600, 1610, 1605], 0.02,
     "unchanged"),
])
def test_a_checks_verdicts(name, a, b, bound, told):
    assert aa.verdict(name, a, b, bound) == told


def test_setup_is_told_by_its_median_alone():
    a, b = [20, 17, 23, 20, 18, 22], [21, 16, 24, 20, 19, 23]
    assert aa.verdict("setup_s", a, b, 0.1) == "unresolved"
    assert aa.verdict("setup_s", a, b, 0.1, by_median_alone=True) \
        == "unchanged"
    assert aa.verdict("setup_s", a, [x + 5 for x in a], 0.1,
                      by_median_alone=True) == "regressed"


def _row(label, seed, tps, ttft, trace=0, correct=True):
    return {"label": label, "workload": "cell", "seed": seed, "trace": trace,
            "rc": 0, "wall_s": 80.0, "result": {
                "correct": correct, "metrics": {
                    "serve_tokens_per_s": {"value": tps, "unit": "tokens/s"},
                    "ttft_p95_ms": {"value": ttft, "unit": "ms"}},
                "window": {"ttft_p90_ms": ttft - 5, "requests": 1000,
                           "seconds": 40.0, "ttft_p95_ms": ttft},
                "setup": {"warm_s": 3.0, "programs": {"programs": 9}}}}


def test_the_table_reads_the_files_a_call_each(tmp_path):
    paths = []
    for call in ("aa_1", "aa_2"):
        p = tmp_path / f"{call}.jsonl"
        rows = [_row("cold", 1, 1400.0, 90.0)]
        for i in range(6):
            rows += [_row("A", 10 + i, 1500.0 + i, 60.0 + i),
                     _row("B", 10 + i, 1501.0 + i, 60.5 + i)]
        rows.append(_row("traced", 99, 1450.0, 70.0, trace=1))
        p.write_text("".join(json.dumps(r) + "\n" for r in rows))
        paths.append(str(p))
    text = aa.table(paths, {"ttft_p95_ms": 0.1, "serve_tokens_per_s": 0.01})
    assert "28 runs in 2 calls, 0 not correct" in text
    # the cold and the traced run are in no set; the window's candidates and
    # the set-up's parts are read beside the metrics
    sets = aa.sets_of(aa.load(paths))
    assert sorted(sets) == [("cell", c, s) for c in ("aa_1", "aa_2")
                            for s in "AB"]
    assert all(len(v) == 6 for v in sets.values())
    assert {"serve_tokens_per_s", "ttft_p95_ms", "ttft_p90_ms", "requests",
            "setup.warm_s"} == set(sets[("cell", "aa_1", "A")][0])
    told = [ln for ln in text.splitlines() if ln.startswith("| cell |")]
    assert len(told) == 4 and all(ln.endswith("| unchanged |") for ln in told)
    # a metric that the cell does not report is not judged there
    text = aa.table(paths, {"ttft_p95_ms": 0.1}, {"ttft_p95_ms": ["other"]})
    assert not [ln for ln in text.splitlines() if ln.startswith("| cell |")]
    text = aa.table(paths, {"ttft_p95_ms": 0.1, "serve_tokens_per_s": 0.01})
    # 60..65 with the farthest left out: 4 of 62.5, times 1.5, up to 0.005
    assert "| `ttft_p95_ms` | 0.0640 | 0.100 |" in text


def test_a_run_that_is_not_correct_is_named(tmp_path):
    p = tmp_path / "aa_1.jsonl"
    p.write_text(json.dumps(_row("A", 5, 1.0, 1.0, correct=False)) + "\n"
                 + json.dumps(dict(_row("B", 5, 1.0, 1.0), result=None))
                 + "\n")
    text = aa.table([str(p)], {})
    assert "2 not correct" in text and text.count("NOT CORRECT") == 2
