"""The arithmetic of the comparison that decides `correct`, on numbers made
by hand."""
import json

import pytest

from perfbench import compare


def test_serve_readings_by_hand():
    gaps = [0.0, 0.0, 0.02, 0.0, 0.04, 0.0, 0.0, 0.0]
    checks = compare.serve_checks(gaps, 0, {"logit_gap": 0.05,
                                            "flip_gap_mean": 0.035,
                                            "token_mismatch_share": 0.3})
    assert checks["logit_gap"]["value"] == 0.04
    assert checks["flip_gap_mean"]["value"] == pytest.approx(0.03)
    assert checks["token_mismatch_share"]["value"] == 0.25
    assert checks["logit_gap"]["tokens"] == 8
    assert compare.all_ok(checks)
    # the mean of the tokens that differ does not fall when more tokens agree
    more = compare.serve_checks(gaps + [0.0] * 92, 0, {})
    assert more["flip_gap_mean"]["value"] == pytest.approx(0.03)


def test_every_token_the_references_first_reads_nought():
    checks = compare.serve_checks([0.0] * 5, 0, {"logit_gap": 0.01,
                                                 "flip_gap_mean": 0.01,
                                                 "token_mismatch_share": 0.1})
    assert [checks[k]["value"] for k in ("logit_gap", "flip_gap_mean",
                                         "token_mismatch_share")] == [0, 0, 0]
    assert compare.all_ok(checks)


@pytest.mark.parametrize("gaps, limits, failing", [
    ([0.0, 0.2], {"logit_gap": 0.3, "flip_gap_mean": 0.1,
                  "token_mismatch_share": 1.0}, "flip_gap_mean"),
    # a limit that is missing fails; it is not read as "no limit"
    ([0.0, 0.2], {"logit_gap": 0.3, "token_mismatch_share": 1.0},
     "flip_gap_mean"),
    # nothing compared: no finished greedy request to read
    ([], {"logit_gap": 0.3, "flip_gap_mean": 0.3,
          "token_mismatch_share": 1.0}, "logit_gap"),
])
def test_what_fails(gaps, limits, failing):
    checks = compare.serve_checks(gaps, 0, limits)
    assert not checks[failing]["ok"]
    assert not compare.all_ok(checks)
    json.loads(json.dumps(checks, allow_nan=False))   # still a JSON line


def test_not_compared_is_printed_and_judged_by_nothing():
    checks = compare.serve_checks([0.0, 5.0], 0, {
        "logit_gap": compare.NOT_COMPARED, "flip_gap_mean": 6.0,
        "token_mismatch_share": compare.NOT_COMPARED})
    assert checks["logit_gap"] == {"value": 5.0, "limit": None, "ok": True,
                                   "compared": False, "tokens": 2}
    assert compare.all_ok(checks)


def test_a_wrong_answer_fails_whatever_the_logits_say():
    checks = compare.serve_checks([0.0], 1, {"logit_gap": 1, "flip_gap_mean": 1,
                                             "token_mismatch_share": 1})
    assert not compare.all_ok(checks)


def test_norm_gap_is_the_gap_of_norms_over_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    g = compare.norm_gap(prog, ref, ["a", "b", "c"])
    assert g["a"] == pytest.approx(0.1)
    assert g["b"] == 0
    assert g["c"] == pytest.approx(1e-9)      # over the median leaf's 1.0
    assert compare.moved_leaves(ref) == ["a", "b"]
