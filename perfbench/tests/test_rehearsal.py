"""A CPU rehearsal of the whole of a run at a toy size, with the device
check lifted here and nowhere else; the planted faults, each of which has to
turn `correct` false; and the control (the reference in fp8 put in the
program's place), which has to fail a limit that the program keeps.

The toy cells live under perfbench/tests/data and are never listed in the
repo's BENCHMARK.json. Nothing here is a measurement: the platform is the CPU
and the result lines say so.
"""
import json
import os
import time

import pytest

from perfbench import check_manifest, compare, harness, reference, run, \
    traffic

BASE = os.path.join(harness.HERE, "tests", "data")
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(BASE, "BENCHMARK.json"))


def _run(name, manifest, trace=False, seconds=1.0, seed=SEED):
    return run.run_cell(name, seed, seconds, trace, manifest=manifest,
                        base=BASE, t_start=time.perf_counter())


def _assert_line(result, manifest, cell, trace):
    line = json.loads(json.dumps(result))      # it has to be JSON
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m for m in harness.cell_metrics(manifest, cell,
                                                         group)}
    assert set(line["metrics"]) <= set(listed)
    for name, m in line["metrics"].items():
        assert m["unit"] == listed[name]["unit"]
        assert m["value"] > 0
    return line


@pytest.mark.parametrize("trace", [False, True])
def test_train_cell_end_to_end(manifest, trace):
    line = _assert_line(_run("tiny-train", manifest, trace), manifest,
                        "tiny-train", trace)
    assert line["correct"] is True and line["failed"] == 0
    if trace:
        assert "step_p50_ms.train" in line["metrics"]
        # no chip: no share of a peak, no roofline, no idle share
        assert not {"step_mfu.train", "flash_attn_roofline.train",
                    "device_idle.train"} & set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("trace", [False, True])
def test_serve_cell_end_to_end(manifest, trace):
    line = _assert_line(_run("tiny-serve", manifest, trace, seconds=2.0),
                        manifest, "tiny-serve", trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 6
    if trace:
        assert {"decode_tick_p50_ms.serve", "tokens_per_tick.serve",
                "prefix_hit_share.serve"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                        "gap_p95_ms", "setup_s"}


def test_a_serve_result_says_where_set_up_went_and_what_the_window_held(
        manifest):
    line = json.loads(json.dumps(_run("tiny-serve", manifest, True,
                                      seconds=2.0)))
    got, e2e = line["metrics"], line["end_to_end_of_this_run"]
    # the two parts of set-up are all of it
    assert got["setup_build_s.serve"]["value"] \
        + got["setup_warm_s.serve"]["value"] == pytest.approx(e2e["setup_s"])
    assert set(line["setup"]) == {"start_s", "imports_weights_s", "engine_s",
                                  "warm_s", "programs"}
    assert line["setup"]["programs"]["programs"] >= 0
    w = line["window"]
    # the clients ran the cell's warm_in_seconds before the window opened,
    # and nothing was built once they ran
    # (and the counters' baseline was read, which a busy test run slows)
    assert 0.5 <= w["warm_in_s"] < 1.0
    assert w["compiles"] == 0 and w["compiles_warm_in"] == 0
    assert line["checks"]["compiles_in_window"] == {
        "value": 0.0, "limit": 0.0, "ok": True}
    assert line["attempted"] == w["requests"]
    # every candidate for a tail from the one list, in order
    assert w["ttft_p50_ms"] <= w["ttft_p90_ms"] <= w["ttft_p95_ms"] \
        <= w["ttft_p99_ms"]
    assert w["ttft_p90_ms"] <= w["ttft_slow10_mean_ms"]
    assert w["gap_p50_ms"] <= w["gap_p95_ms"] <= w["gap_p99_ms"]
    # the end-to-end metrics of a serve run are the window's own
    assert e2e["ttft_p90_ms"] == w["ttft_p90_ms"]
    assert e2e["gap_p95_ms"] == w["gap_p95_ms"]


def test_the_toy_manifest_is_sound_but_for_its_files(manifest):
    # the toy cells' files are under tests/data, so only those faults show
    bad = [b for b in check_manifest.check(manifest)
           if "does not exist" not in b and "no reader" not in b
           and "its file says" not in b]
    assert bad == []


# ---------------------------------------------------------------------------
# planted faults: the timed path broken underneath, correct has to be false
# ---------------------------------------------------------------------------


def _break_step(monkeypatch, wrap):
    from perfbench import job_train

    build = job_train.build

    def broken(cell, seed):
        lm, key = build(cell, seed)
        lm._step = wrap(lm._step)
        return lm, key

    monkeypatch.setattr(job_train, "build", broken)


def test_fault_step_returns_its_state_unchanged(manifest, monkeypatch):
    def wrap(step):
        def same(params, opt, x, y):
            _, _, loss = step(params, opt, x, y)
            return params, opt, loss
        return same

    _break_step(monkeypatch, wrap)
    line = _run("tiny-train", manifest)
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert not line["checks"]["grad_norm_gap"]["ok"]


def test_fault_half_of_the_batch_left_out(manifest, monkeypatch):
    def wrap(step):
        def half(params, opt, x, y):
            n = x.shape[0] // 2
            return step(params, opt, x[:n], y[:n])
        return half

    _break_step(monkeypatch, wrap)
    line = _run("tiny-train", manifest)
    assert line["correct"] is False
    assert not line["checks"]["grad_norm_gap"]["ok"]


def test_fault_a_token_altered_where_it_is_produced(manifest, monkeypatch):
    from deeplearning4j_tpu.serving import paged

    sample = paged._sample_step

    def altered(logits, keys, temps):
        nxt, nkeys = sample(logits, keys, temps)
        return (nxt + 1) % logits.shape[-1], nkeys

    monkeypatch.setattr(paged, "_sample_step", altered)
    monkeypatch.setattr(paged, "_PAGED_TICK_CACHE", {})
    line = _run("tiny-serve", manifest, seconds=2.0)
    assert line["correct"] is False
    assert not line["checks"]["logit_gap"]["ok"]


def test_fault_a_prefill_width_the_warm_up_left_out(manifest, monkeypatch):
    """A warm-up that reaches the shortest prompt's width alone: the others
    are built once the clients run, which no metric owns up to, and
    `correct` has to be false by `compiles_in_window`."""
    from deeplearning4j_tpu.serving import paged

    monkeypatch.setattr(traffic, "warm_lengths", lambda mix: [
        mix["system_tokens"] + mix["user_tokens"]["min"]])
    # programs of earlier tests in this process would stand in for set-up's
    monkeypatch.setattr(paged, "_PAGED_ADMIT_CACHE", {})
    line = _run("tiny-serve", manifest, seconds=2.0)
    assert line["correct"] is False
    built = line["checks"]["compiles_in_window"]
    assert built["value"] >= 1 and not built["ok"]
    assert built["value"] == line["window"]["compiles"] \
        + line["window"]["compiles_warm_in"]
    assert line["checks"]["logit_gap"]["ok"]


def test_fault_an_answer_cut_short(manifest, monkeypatch):
    from perfbench import loadgen

    parse = loadgen.ClosedLoop._parse

    def lossy(self, req, now):
        parse(self, req, now)
        if req.finished and len(req.tokens) > 1:
            req.tokens.pop()            # says the wrong thing: a token short

    monkeypatch.setattr(loadgen.ClosedLoop, "_parse", lossy)
    line = _run("tiny-serve", manifest, seconds=1.0)
    assert line["correct"] is False
    assert not line["checks"]["malformed_answers"]["ok"]


# ---------------------------------------------------------------------------
# the control: the reference, in fp8, in the program's place
# ---------------------------------------------------------------------------


def test_control_fp8_fails_the_training_limits():
    cell = harness.load_cell("tiny-train", BASE)
    conf, mix = cell["conf"], cell["mix"]
    key = harness.seed_key(SEED)
    batches = [traffic.train_batch(mix, conf["vocab_size"], SEED, k)
               for k in range(3)]
    ref = reference.train_reference(conf, key, batches)
    ctrl = reference.train_reference(conf, key, batches, lowp="fp8")
    checks = compare.train_checks(ctrl, ref, cell["limits"])
    assert not compare.all_ok(checks)
    assert not checks["grad_norm_gap"]["ok"]
    # and the reference against itself is exact
    same = compare.train_checks(ref, ref, cell["limits"])
    assert compare.all_ok(same)
    assert all(c["value"] == 0 for c in same.values())


def test_control_fp8_puts_other_tokens_first():
    """Serving's control at a size a test can hold: over a few hundred
    positions the fp8 reference's first token lies below the float32
    reference's best by more than the toy cell's limit, somewhere."""
    import jax
    import numpy as np

    cell = harness.load_cell("tiny-serve", BASE)
    conf = dict(cell["conf"], vocab_size=4096, n_positions=128)
    params = jax.jit(lambda k: reference.init_params(
        conf, k, **cell["weights"]))(harness.seed_key(SEED))
    rng = np.random.default_rng(SEED)
    widest = 0.0
    for _ in range(4):
        prompt = rng.integers(0, 4096, 32)
        served = rng.integers(0, 4096, 90)
        gaps = reference.serve_gaps(conf, params, prompt, served, 128,
                                    lowp="fp8")
        assert gaps.shape == (90,) and (gaps >= 0).all()
        widest = max(widest, float(gaps.max()))
    assert widest > cell["limits"]["logit_gap"]
    # the float32 reference judged by itself puts its own best first
    own = reference.serve_gaps(conf, params, prompt, served, 128)
    assert (own >= 0).all()
