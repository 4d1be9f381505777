"""The `serve_sambay` job kind and what came with it: the operation and byte
counts of perfbench/flops_phi4flash.py against hand counts, the four new
readers on hand-built fixtures, the fp8 and bf16-state controls, and a CPU
rehearsal of a tiny cell of the same shape through `perfbench/run.py`'s
`run_cell`, with two planted faults. The toy cell lives under
perfbench/tests/data_sambay. Nothing here is a measurement.

Reference anchor: none in the reference (it has no benchmark of a served
model); the cell's contract is PERF.md sections 2 to 4.
"""
import json
import os
import time

import numpy as np
import pytest

from perfbench import flops_phi4flash as fl
from perfbench import harness, job_serve_moe, job_serve_sambay, run
from perfbench import sambay_reduce

BASE = os.path.join(harness.HERE, "tests", "data_sambay")
SEED = 2**31 + 97
CELL = "tiny-serve-sambay"
PUBLISHED_CELL = "serve-phi4-mini-flash-agent16k"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(BASE, "BENCHMARK.json"))


def _published():
    return harness.load_json(os.path.join(
        harness.HERE, "configs", "phi-4-mini-flash-reasoning.json"))


# ---------------------------------------------------------------------------
# operations and bytes by shape
# ---------------------------------------------------------------------------


def test_published_counts_against_the_hand_count():
    conf = _published()
    mlp = 3 * 2560 * 10240
    lp = fl.layer_matmul_params(conf)
    assert lp == {
        "mamba": 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 + mlp,
        "attention": 2560 * 5120 + 2560 * 2560 + mlp,
        "cross": 2 * 2560 * 2560 + mlp, "gmu": 2 * 2560 * 5120 + mlp,
        "kv_projection": 2 * 2560 * 1280}
    # a decoded token: every layer and the tied head, 3.85 B
    assert fl.tick_params(conf) == 9 * lp["mamba"] + 9 * lp["attention"] \
        + 7 * lp["cross"] + 7 * lp["gmu"] + 200_064 * 2560
    # an admitted token: the self-decoder and layer 17's K and V, 3.74 GFLOP
    assert fl.admit_params(conf) == 9 * lp["mamba"] + 8 * lp["attention"] \
        + lp["kv_projection"]
    assert 2 * fl.admit_params(conf) / 1e9 == pytest.approx(3.74, abs=0.01)
    assert fl.scan_flops(conf) == 9 * (6 * 5120 * 16 + 3 * 5120)
    assert fl.kv_bytes_per_token(conf) == 5120
    assert fl.scan_bytes(conf, 1024) == 9 * 4 * 1024 * (3 * 5120 + 32)


def test_attention_pairs_are_capped_at_the_window():
    conf = _published()
    assert fl.window_pairs(conf, 100) == 5050
    assert fl.window_pairs(conf, 1000) == 512 * 513 / 2 + 488 * 512
    assert fl.admit_layer_pairs(conf, 1000) == 8 * fl.window_pairs(conf, 1000)
    assert fl.attention_flops(conf, 10) == 6 * 10 * 64 * 40
    work = fl.serve_flops(conf, 3, 100, 20)
    assert work == (2 * fl.tick_params(conf) + fl.scan_flops(conf)) * 3 \
        + (2 * fl.admit_params(conf) + fl.scan_flops(conf)) * 100 \
        + fl.attention_flops(conf, 20)


# ---------------------------------------------------------------------------
# the readers on hand-built fixtures
# ---------------------------------------------------------------------------


class _Trace:
    def __init__(self, names):
        self.device_ops = {0: [(i * 1.0, i * 1.0 + 0.5, n)
                               for i, n in enumerate(names)]}


MOSAIC = 'custom_call_target="tpu_custom_call"'
STATE = ("%fusion.3 = f32[64,16,5120]{2,1,0} fusion(f32[64,16,5120]{2,1,0} "
         "%p, f32[64,5120]{1,0} %d)")
SHARED = ("%decode_attn.3 = f32[64,40,128]{2,1,0} custom-call(s32[65536]{0} "
          "%t, bf16[32769,16,1280]{2,1,0} %k, bf16[32769,16,1280]{2,1,0} %v), "
          + MOSAIC)
WINDOW = ("%decode_attn.1 = f32[64,40,128]{2,1,0} custom-call(s32[65536]{0} "
          "%t, bf16[2177,16,1280]{2,1,0} %k, bf16[2177,16,1280]{2,1,0} %v), "
          + MOSAIC)
SCAN = ("%selective_scan.2 = (f32[1024,5120]{1,0}, f32[16,5120]{1,0}) "
        "custom-call(f32[16,5120]{1,0} %a, f32[1024,5120]{1,0} %d), " + MOSAIC)
OTHER = "%fusion.1 = f32[64,2560]{1,0} fusion(f32[64,2560]{1,0} %h)"
TICKS = [{"selective_state_bytes": 2 * 47 * 2_949_120,
          "shared_kv_live": 8 * 300_000, "admit_width_sum": 1024},
         {"selective_state_bytes": 2 * 48 * 2_949_120,
          "shared_kv_live": 8 * 310_000, "admit_width_sum": 0}]
KV = {"lm": {"lanes": 64, "state_lanes": 64, "block_tokens": 16,
             "groups": [{"layers": 1, "window": 0, "blocks": 32768},
                        {"layers": 8, "window": 512, "blocks": 2176}]}}


def _ctx(names, ticks, kv=KV):
    return {"conf": _published(), "trace": _Trace(names), "kv": kv,
            "traced": {"ticks": [{"attrs": a} for a in ticks]},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_each_reader_finds_its_own_events():
    ctx = _ctx([STATE, SHARED, WINDOW, SCAN, OTHER, STATE, SHARED], TICKS)
    hbm = 819e9
    assert sambay_reduce.state_tail(ctx) == {
        "ticks": 2.0, "bytes": 2 * 95 * 2_949_120.0, "seconds": 1.0}
    assert harness.load_reader("selective_step_ms_per_tick.serve_sambay"
                               ).read(ctx) == pytest.approx(500.0)
    # the window pool's buffers are not the shared pool's
    assert sambay_reduce.shared_tail(ctx) == {
        "positions": 8 * 610_000.0, "seconds": 1.0}
    assert harness.load_reader("shared_kv_roofline.serve_sambay").read(
        ctx) == pytest.approx(100 * (8 * 610_000 * 5120 / hbm) / 1.0)
    assert sambay_reduce.scan_tail(ctx) == {"width": 1024.0, "seconds": 0.5}
    assert harness.load_reader("selective_scan_roofline.serve_sambay").read(
        ctx) == pytest.approx(100 * (fl.scan_bytes(ctx["conf"], 1024) / hbm)
                              / 0.5)


def test_nothing_to_read_is_no_number():
    readers = [harness.load_reader(n) for n in (
        "selective_step_ms_per_tick.serve_sambay",
        "shared_kv_roofline.serve_sambay",
        "selective_scan_roofline.serve_sambay")]
    events = [STATE, SHARED, SCAN]
    for ctx in (_ctx(events, [{"lanes": 3}]),           # the parent's ticks
                _ctx([OTHER, WINDOW], TICKS),           # no such event
                _ctx(events, [])):                      # no tick traced
        assert all(r.read(ctx) is None for r in readers)
    # no decoder's report: no pool's shape to look for
    assert all(r.read(_ctx(events, TICKS, kv={})) is None
               for r in readers[:2])
    other_model = dict(_ctx(events, TICKS), conf={"hidden_size": 2048})
    assert readers[0].read(other_model) is None
    assert all(r.read(dict(_ctx(events, TICKS), peaks=None)) is None
               for r in readers[1:])
    mfu = harness.load_reader("step_mfu.serve_sambay")
    assert mfu.read({"peaks": None}) is None
    assert mfu.read({"peaks": {}, "spans": []}) is None


def test_step_mfu_counts_ticks_admissions_and_pairs(monkeypatch):
    from perfbench import span_reduce

    conf = _published()
    ticks = [{"attrs": {"kv_live": 1000, "shared_kv_live": 8000}},
             {"attrs": {"kv_live": 3000, "shared_kv_live": 24000}}]
    admits = [{"attrs": {"prompt_tokens": 8192}}, {"attrs": {}}]
    monkeypatch.setattr(span_reduce, "window_spans",
                        lambda ctx, name: admits)
    ctx = {"peaks": {"bf16_flops": 197e12}, "spans": ticks, "conf": conf,
           "cell": {"chips": 1},
           "window": {"prefill_tokens": 8192, "decode_tokens": 64,
                      "seconds": 2.0}}
    pairs = 9 * 4000 + fl.admit_layer_pairs(conf, 8192)
    work = fl.serve_flops(conf, 64, 8192, pairs)
    got = harness.load_reader("step_mfu.serve_sambay").read(ctx)
    assert got == pytest.approx(100 * work / 2.0 / 197e12)
    # a program whose ticks do not count the shared reads: no number
    del ticks[0]["attrs"]["shared_kv_live"]
    assert harness.load_reader("step_mfu.serve_sambay").read(ctx) is None


def test_the_job_builds_the_model_first_and_reads_at_the_smallest_width():
    with pytest.raises(ValueError, match="no model"):
        job_serve_sambay.build({"conf": {"model_type": "gpt2"}})
    cell = harness.load_cell(PUBLISHED_CELL)
    model_of, reference, leaf = job_serve_sambay.build(cell)
    assert leaf == "selective" and reference.__name__.endswith("phi4flash")
    widths = tuple(cell["reference_widths"])
    rows = cell["mix"]["output_tokens"]["max"]
    assert job_serve_moe.width_for(1024, rows, widths) == 2048
    assert job_serve_moe.width_for(15_360, rows, widths) == 16_384


def test_the_warm_up_reaches_every_prefill_width_of_the_cell():
    from deeplearning4j_tpu.ops import dispatch
    from perfbench import traffic

    mix = harness.load_cell(PUBLISHED_CELL)["mix"]
    lo, hi = mix["user_tokens"]["min"], mix["user_tokens"]["max"]
    every = {dispatch.bucket_size(n) for n in range(lo, hi + 1)}
    warmed = [dispatch.bucket_size(n) for n in traffic.warm_lengths(mix)]
    assert set(warmed) == every and max(every) == 16_384
    assert hi + mix["output_tokens"]["max"] <= 16_384


# ---------------------------------------------------------------------------
# the rehearsal: a tiny cell end to end on the CPU
# ---------------------------------------------------------------------------


def _run(manifest, trace=False, seconds=2.0, seed=SEED):
    return run.run_cell(CELL, seed, seconds, trace, manifest=manifest,
                        base=BASE, t_start=time.perf_counter())


@pytest.mark.parametrize("trace", [False, True])
def test_sambay_cell_end_to_end(manifest, trace):
    line = json.loads(json.dumps(_run(manifest, trace)))
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 6
    checks = line["checks"]
    assert checks["logit_gap"]["tokens"] >= 20
    assert checks["state_bits_lost"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0
    if trace:
        assert {"decode_tick_p50_ms.serve", "tokens_per_tick.serve",
                "kv_live_share.serve", "setup_build_s.serve"} \
            <= set(line["metrics"])
        # no chip: no share of a peak or of a roofline, no device time
        assert not {"step_mfu.serve_sambay",
                    "shared_kv_roofline.serve_sambay",
                    "selective_step_ms_per_tick.serve_sambay",
                    "selective_scan_roofline.serve_sambay",
                    "device_idle.serve"} & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                        "gap_p95_ms", "setup_s"}


def test_fault_the_cross_decoder_reads_no_shared_keys(manifest,
                                                      monkeypatch):
    """A planted fault: the admission writes no shared keys and values, so
    the cross-attention layers read an empty past. Every answer then
    misses the prompt in half its layers, and `correct` has to be false."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.serving import paged

    admit = hybrid.paged_admit

    def forgetful(params, arena, window, write_table, lane, cfg, **kw):
        out = admit(params, arena, window, write_table, lane, cfg, **kw)
        src = cfg.n_attention - 1
        keep = lambda new, old: tuple(old[j] if j == src else new[j]
                                      for j in range(len(new)))
        return dict(out, k=keep(out["k"], arena["k"]),
                    v=keep(out["v"], arena["v"]))

    monkeypatch.setattr(hybrid, "paged_admit", forgetful)
    monkeypatch.setattr(paged, "_PAGED_ADMIT_CACHE", {})
    line = _run(manifest)
    assert line["correct"] is False
    assert not line["checks"]["logit_gap"]["ok"]


def test_fault_a_selective_state_kept_in_bfloat16(manifest, monkeypatch):
    """The fault that served tokens cannot see: the tick leaves S rounded
    to bfloat16. `state_bits_lost` reads the pool itself, finds 16 of
    float32's 23 mantissa bits unused, and `correct` is false."""
    from jax import lax

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.serving import paged

    step = hybrid.selective_step

    def rounded(u, state, tail, sp, cfg):
        out, state, tail, y = step(u, state, tail, sp, cfg)
        return out, lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=7), tail, y

    monkeypatch.setattr(hybrid, "selective_step", rounded)
    monkeypatch.setattr(paged, "_PAGED_TICK_CACHE", {})
    line = _run(manifest)
    checks = line["checks"]
    assert line["correct"] is False
    assert checks["state_bits_lost"]["value"] == 16.0
    assert not checks["state_bits_lost"]["ok"]


def test_the_controls_move_what_each_should():
    """The fp8 control at a size a test can hold puts other tokens first
    by more than the toy cell's limit somewhere; the bf16-state control
    leaves the state with 16 of its 23 mantissa bits unused, where the
    float32 scan leaves none."""
    import jax

    from perfbench import reference_phi4flash as reference
    from perfbench.job_serve_hybrid import mantissa_bits_lost

    cell = harness.load_cell(CELL, BASE)
    conf = dict(cell["conf"], vocab_size=4096)
    params = jax.jit(lambda k: reference.init_params(
        conf, k, **cell["weights"]))(harness.seed_key(SEED))
    rng = np.random.default_rng(SEED)
    prompt = rng.integers(0, 4096, 20)
    served = rng.integers(0, 4096, 40)
    gaps = reference.serve_gaps(conf, params, prompt, served, 64,
                                lowp="fp8", rows=44)
    assert gaps.shape == (40,) and (gaps >= 0).all()
    assert float(gaps.max()) > cell["limits"]["logit_gap"]
    seq = np.concatenate([prompt, served])[:-1]
    assert mantissa_bits_lost([reference.state_one(params, seq, conf)]) == 0
    assert mantissa_bits_lost([reference.state_one(
        params, seq, conf, "state_bf16")]) == 16
    with pytest.raises(ValueError, match="unknown lower precision"):
        reference.logits_one(params, seq, conf, "int4")
    with pytest.raises(ValueError, match="does not fit"):
        reference.serve_gaps(conf, params, prompt, served, 32)
