"""The `serve_gdn` job kind and what came with it: the operation and byte
counts of perfbench/flops_qwen3_next.py against hand counts, the four new
readers on hand-built fixtures, the fp8 and bf16-state controls, and a CPU
rehearsal of a tiny cell of the same shape through `perfbench/run.py`'s
`run_cell`, with two planted faults. The toy cell lives under
perfbench/tests/data_gdn. Nothing here is a measurement.

Reference anchor: none in the reference (it has no benchmark of a served
model); the cell's contract is PERF.md sections 2 to 4.
"""
import json
import os
import time

import numpy as np
import pytest

from perfbench import flops_qwen3_next as fl
from perfbench import gdn_reduce, harness, job_serve_gdn, job_serve_moe, run

BASE = os.path.join(harness.HERE, "tests", "data_gdn")
SEED = 2**31 + 93
CELL = "tiny-serve-gdn"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(BASE, "BENCHMARK.json"))


def _published():
    return harness.load_json(os.path.join(
        harness.HERE, "configs", "qwen3-next-80b-a3b-stage8-ep4.json"))


# ---------------------------------------------------------------------------
# operations and bytes by shape
# ---------------------------------------------------------------------------


def test_published_counts_against_the_hand_count():
    conf = _published()
    mp = fl.matmul_params(conf)
    assert mp == {
        "gdn_proj": 6 * (2048 * 12288 + 2048 * 64 + 4096 * 2048),
        "attn_proj": 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048),
        "router": 8 * 2048 * 512, "shared": 8 * (3 * 2048 * 512 + 2048),
        "head": 37_984 * 2048}
    assert fl.expert_params(conf) == 3 * 2048 * 512 == 3_145_728
    assert fl.expert_bytes(conf) == 6_291_456
    # the stage's weights: what the configuration's file states
    count = conf["parameters"]
    assert count["total"] == 3_667_251_328
    assert count["gdn_layer"] == 440_572_096
    assert count["attention_layer"] == 434_117_120
    assert count["bytes_bfloat16"] == 7_334_502_656
    wb = fl.weight_bytes(conf)
    assert wb["experts"] == 8 * 128 * 6_291_456 == 6_442_450_944
    # a lane's S over 6 layers: 32 heads x 128 x 128 x 4 B
    assert fl.gdn_state_bytes(conf) == 6 * 32 * 128 * 128 * 4 == 12_582_912
    # 4 KB a token: 2 layers x 2 KV heads x 256 x K and V x 2 B
    assert fl.kv_bytes_per_token(conf) == 4096
    # a token reaches 10 of 512 experts a layer, a quarter of them held
    assert fl.used_by_a_token(conf) == sum(mp.values()) \
        + 8 * 10 * 128 / 512 * 3_145_728


def test_the_delta_rule_and_attention_counts():
    conf = _published()
    assert fl.delta_rule_flops(conf) == 6 * 32 * 7 * 128 * 128
    per_head = 4 * 64 * 128 + 64 * 256 + 2 * 64 * 128 + 6 * 128 * 128
    assert fl.delta_rule_chunked_flops(conf) == 6 * 32 * per_head
    assert fl.prefill_layer_pairs(conf, 100) == 2 * 5050
    assert fl.attention_flops(conf, 10) == 4 * 10 * 256 * 16
    mp = sum(fl.matmul_params(conf).values())
    assert fl.forward_flops(conf, 3, 40, 20) == \
        (2 * mp + fl.delta_rule_flops(conf)) * 3 + 2 * 3_145_728 * 40 \
        + 4 * 20 * 256 * 16


# ---------------------------------------------------------------------------
# the readers on hand-built fixtures
# ---------------------------------------------------------------------------


class _Trace:
    def __init__(self, names):
        self.device_ops = {0: [(i * 1.0, i * 1.0 + 0.5, n)
                               for i, n in enumerate(names)]}


def _ctx(names, ticks, lanes=64):
    return {"conf": _published(), "trace": _Trace(names),
            "kv": {"lm": {"lanes": lanes, "state_lanes": lanes}},
            "traced": {"ticks": [{"attrs": a} for a in ticks]},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


STATE = ("%fusion.3 = f32[64,32,128,128]{3,2,1,0} fusion(f32[64,32,128,128]"
         "{3,2,1,0} %p, f32[64,32,128]{2,1,0} %d)")
READ_ONLY = ("%fusion.4 = f32[64,32,128]{2,1,0} fusion(f32[64,32,128,128]"
             "{3,2,1,0} %p, f32[64,32,128]{2,1,0} %k)")
UP = ("%fusion.7 = f32[128,64,1024]{2,1,0} fusion(bf16[64,2048]{1,0} %x, "
      "bf16[128,2048,1024]{2,1,0} %w_in)")
DOWN = ("%fusion.8 = f32[64,2048]{1,0} fusion(bf16[128,64,512]{2,1,0} %m, "
        "bf16[128,512,2048]{2,1,0} %w_down)")
ADMIT = ("%ragged-dot.9 = f32[40960,1024]{1,0} custom-call(s32[129]{0} %a, "
         "bf16[40960,2048]{1,0} %xs, bf16[128,2048,1024]{2,1,0} %w)")
OTHER = "%fusion.1 = f32[64,2048]{1,0} fusion(f32[64,2048]{1,0} %h)"
TICKS = [{"gdn_state_bytes": 2 * 31 * 12_582_912, "moe_experts_hit": 500},
         {"gdn_state_bytes": 2 * 32 * 12_582_912, "moe_experts_hit": 520}]


def test_the_state_update_is_found_by_the_shape_it_reads_and_writes():
    """The pass that writes S' and the pass that only reads S are both the
    update's time; the expert products and the rest are not."""
    ctx = _ctx([STATE, READ_ONLY, UP, DOWN, OTHER, STATE], TICKS)
    assert [e[2] for e in gdn_reduce.state_events(ctx)] \
        == [STATE, READ_ONLY, STATE]
    t = gdn_reduce.tail(ctx)
    assert t == {"ticks": 2.0, "bytes": 2 * 63 * 12_582_912.0,
                 "seconds": 1.5}
    roof = harness.load_reader("gdn_step_roofline.serve_gdn").read(ctx)
    assert roof == pytest.approx(100 * (2 * 63 * 12_582_912 / 819e9) / 1.5)
    per_tick = harness.load_reader("gdn_step_ms_per_tick.serve_gdn").read(
        ctx)
    assert per_tick == pytest.approx(750.0)


def test_the_held_experts_products_are_found_by_what_they_read():
    ctx = _ctx([UP, DOWN, ADMIT, STATE, OTHER], TICKS)
    t = gdn_reduce.expert_tail(ctx)
    assert t == {"ticks": 2.0, "hit": 1020.0, "seconds": 1.0}
    roof = harness.load_reader("moe_experts_roofline.serve_gdn").read(ctx)
    assert roof == pytest.approx(100 * (1020 * 6_291_456 / 819e9) / 1.0)


def test_nothing_to_read_is_no_number():
    readers = [harness.load_reader(n) for n in (
        "gdn_step_roofline.serve_gdn", "gdn_step_ms_per_tick.serve_gdn",
        "moe_experts_roofline.serve_gdn")]
    for ctx in (_ctx([STATE, UP], [{"lanes": 3}]),     # the parent's ticks
                _ctx([OTHER, ADMIT], TICKS),           # no such event
                _ctx([STATE, UP], []),                 # no tick traced
                _ctx([STATE, UP], TICKS, lanes=0)):    # no decoder
        assert all(r.read(ctx) is None for r in readers)
    other_model = dict(_ctx([STATE, UP], TICKS), conf={"hidden_size": 2048})
    assert all(r.read(other_model) is None for r in readers)
    assert all(r.read(dict(_ctx([STATE, UP], TICKS), peaks=None)) is None
               for r in (readers[0], readers[2]))
    mfu = harness.load_reader("step_mfu.serve_gdn")
    assert mfu.read({"peaks": None}) is None
    assert mfu.read({"peaks": {}, "spans": []}) is None


def test_step_mfu_counts_dense_work_held_pairs_and_attention(monkeypatch):
    from perfbench import span_reduce

    conf = _published()
    ticks = [{"attrs": {"kv_live": 1000, "moe_pairs_held": 80,
                        "admit_moe_pairs_held": 0}},
             {"attrs": {"kv_live": 3000, "moe_pairs_held": 70,
                        "admit_moe_pairs_held": 20_000}}]
    admits = [{"attrs": {"prompt_tokens": 8192}}, {"attrs": {}}]
    monkeypatch.setattr(span_reduce, "window_spans",
                        lambda ctx, name: admits)
    ctx = {"peaks": {"bf16_flops": 197e12}, "spans": ticks, "conf": conf,
           "cell": {"chips": 1},
           "window": {"prefill_tokens": 8192, "decode_tokens": 64,
                      "seconds": 2.0}}
    pairs = 2 * 4000 + fl.prefill_layer_pairs(conf, 8192)
    work = fl.forward_flops(conf, 8192 + 64, 20_150, pairs)
    got = harness.load_reader("step_mfu.serve_gdn").read(ctx)
    assert got == pytest.approx(100 * work / 2.0 / 197e12)
    # a program whose ticks do not count held pairs: no number
    del ticks[0]["attrs"]["moe_pairs_held"]
    assert harness.load_reader("step_mfu.serve_gdn").read(ctx) is None


def test_the_job_builds_the_model_first_and_reads_at_the_smallest_width():
    with pytest.raises(ValueError, match="no model"):
        job_serve_gdn.build({"conf": {"model_type": "gpt2"}})
    cell = harness.load_cell("serve-qwen3-next-stage8-longdoc")
    model_of, reference, leaf = job_serve_gdn.build(cell)
    assert leaf == "gdn" and reference.__name__.endswith("qwen3_next")
    widths = tuple(cell["reference_widths"])
    rows = cell["mix"]["output_tokens"]["max"]
    assert job_serve_moe.width_for(512, rows, widths) == 2048
    assert job_serve_moe.width_for(15_360, rows, widths) == 16_384


def test_the_warm_up_reaches_every_prefill_width_of_the_cell():
    from deeplearning4j_tpu.ops import dispatch
    from perfbench import traffic

    mix = harness.load_cell("serve-qwen3-next-stage8-longdoc")["mix"]
    lo, hi = mix["user_tokens"]["min"], mix["user_tokens"]["max"]
    every = {dispatch.bucket_size(n) for n in range(lo, hi + 1)}
    warmed = [dispatch.bucket_size(n) for n in traffic.warm_lengths(mix)]
    assert set(warmed) == every and max(every) == 16_384
    # and the longest request fits the served context
    assert hi + mix["output_tokens"]["max"] <= 16_384


# ---------------------------------------------------------------------------
# the rehearsal: a tiny cell end to end on the CPU
# ---------------------------------------------------------------------------


def _run(manifest, trace=False, seconds=2.0, seed=SEED):
    return run.run_cell(CELL, seed, seconds, trace, manifest=manifest,
                        base=BASE, t_start=time.perf_counter())


@pytest.mark.parametrize("trace", [False, True])
def test_gdn_cell_end_to_end(manifest, trace):
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
        assert not {"step_mfu.serve_gdn", "gdn_step_roofline.serve_gdn",
                    "gdn_step_ms_per_tick.serve_gdn",
                    "moe_experts_roofline.serve_gdn",
                    "device_idle.serve"} & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                        "gap_p95_ms", "setup_s"}


def test_fault_a_lane_admitted_without_its_gdn_state(manifest, monkeypatch):
    """A planted fault: admission writes the blocks and leaves the lane's
    GDN state as it was. Every answer then starts from a state that never
    saw the prompt, and `correct` has to be false."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.serving import paged

    admit = hybrid.paged_admit

    def stateless(params, arena, window, write_table, lane, cfg):
        out, pairs = admit(params, arena, window, write_table, lane, cfg)
        return dict(out, gdn=arena["gdn"], gdn_conv=arena["gdn_conv"]), pairs

    monkeypatch.setattr(hybrid, "paged_admit", stateless)
    monkeypatch.setattr(paged, "_PAGED_ADMIT_CACHE", {})
    line = _run(manifest)
    assert line["correct"] is False
    assert not line["checks"]["logit_gap"]["ok"]


def test_fault_a_gdn_state_kept_in_bfloat16(manifest, monkeypatch):
    """The fault that served tokens cannot see: the tick leaves S rounded
    to bfloat16. `state_bits_lost` reads the pool itself, finds 16 of
    float32's 23 mantissa bits unused, and `correct` is false."""
    from jax import lax

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.serving import paged

    step = hybrid.gdn_step

    def rounded(u, state, tail, lp, cfg):
        out, state, tail = step(u, state, tail, lp, cfg)
        return out, lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=7), tail

    monkeypatch.setattr(hybrid, "gdn_step", rounded)
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

    from perfbench import reference_qwen3_next as reference
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
