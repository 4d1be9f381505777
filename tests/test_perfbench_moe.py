"""The `serve_moe` job kind and what came with it (ISSUE 37): the operation
and byte counts of perfbench/flops_smallthinker.py against hand counts, the
three new readers on hand-built fixtures, the padded lengths the reference
reads at, the fp8 control, and a CPU rehearsal of a tiny cell of the same
shape through `perfbench/run.py`'s `run_cell`, with a planted fault. The toy
cell lives under perfbench/tests/data_moe. Nothing here is a measurement.

Reference anchor: none in the reference (it has no benchmark of a served
model); the cell's contract is PERF.md sections 2 to 4.
"""
import json
import os
import time

import numpy as np
import pytest

from perfbench import flops_smallthinker as fl
from perfbench import harness, job_serve_moe, moe_reduce, run

BASE = os.path.join(harness.HERE, "tests", "data_moe")
SEED = 2**31 + 91
CELL = "tiny-serve-moe"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(BASE, "BENCHMARK.json"))


def _published():
    return harness.load_json(os.path.join(
        harness.HERE, "configs", "smallthinker-21b-a3b-stage12.json"))


def _tiny():
    return harness.load_json(os.path.join(BASE, "configs", "tiny-moe.json"))


# ---------------------------------------------------------------------------
# operations and bytes by shape
# ---------------------------------------------------------------------------


def test_published_counts_against_the_hand_count():
    conf = _published()
    mp = fl.matmul_params(conf)
    assert mp == {"attn_proj": 12 * 20_971_520, "router": 12 * 163_840,
                  "experts_used": 12 * 6 * 3 * 2560 * 768,
                  "head": 151_936 * 2560}
    assert sum(mp.values()) == 1_067_253_760
    # with the norms' scales, what the configuration's file states a token
    # uses: a fifth of what the stage holds
    assert conf["parameters"]["used_by_a_token"] \
        == sum(mp.values()) + 12 * 5120 + 2560 == 1_067_317_760
    assert fl.layer_kinds(conf) == {"global": 3, "window": 9}
    assert fl.expert_bytes(conf) == 3 * 2560 * 768 * 2 == 11_796_480
    wb = fl.weight_bytes(conf)
    assert wb["experts"] == 12 * 64 * 11_796_480 == 9_059_696_640
    assert wb["head"] == 777_912_320
    # 24 KB a token over the 12 layers: 4 KV heads x 128 x K and V x 2 B
    assert fl.kv_bytes_per_token(conf) == 24_576


def test_pairs_are_capped_at_the_window():
    assert fl.causal_pairs(10) == 55
    assert fl.causal_pairs(10, 16) == 55            # inside the window
    assert fl.causal_pairs(10, 4) == 10 + 6 * 4     # 1+2+3+4, then 4 each
    conf = _published()
    n = 8192
    capped = 4096 * 4097 / 2 + (n - 4096) * 4096
    assert fl.causal_pairs(n, 4096) == capped
    assert fl.prefill_layer_pairs(conf, n) \
        == 3 * n * (n + 1) / 2 + 9 * capped
    assert fl.prefill_layer_pairs(conf, 100) == 12 * 5050


def test_tiny_counts_against_the_hand_count():
    conf = _tiny()
    # d 64, 4 heads of 32 over 2 KV heads, 8 experts of 32, top 2, 8 layers
    used = 8 * ((64 * (128 + 64 + 64) + 128 * 64) + 64 * 8
                + 2 * 3 * 64 * 32) + 256 * 64
    assert sum(fl.matmul_params(conf).values()) == used
    assert fl.layer_kinds(conf) == {"global": 2, "window": 6}
    # one token that attends 10 pairs in every layer
    assert fl.forward_flops(conf, 1, 8 * 10) \
        == 2 * used + 4 * 80 * 32 * 4


# ---------------------------------------------------------------------------
# the readers on hand-built fixtures
# ---------------------------------------------------------------------------


class _Trace:
    def __init__(self, names):
        self.device_ops = {0: [(i * 1.0, i * 1.0 + 0.5, n)
                               for i, n in enumerate(names)]}


def _ctx(names, ticks, lanes=48):
    return {"conf": _published(), "trace": _Trace(names),
            "kv": {"lm": {"lanes": lanes}},
            "traced": {"ticks": [{"attrs": a} for a in ticks]},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


TICK_UP = ("%fusion.7 = f32[64,48,1536]{2,1,0} fusion(bf16[48,2560]{1,0} "
           "%x, bf16[64,2560,1536]{2,1,0} %w_in_3_)")
TICK_DOWN = ("%fusion.8 = f32[48,2560]{1,0} fusion(bf16[64,48,768]{2,1,0} "
             "%mid, bf16[64,768,2560]{2,1,0} %w_down_3_)")
TICK_GROUPED = ("%ragged-dot.1 = f32[288,1536]{1,0} custom-call(s32[65]{0} "
                "%a, bf16[288,2560]{1,0} %xs, bf16[64,2560,1536]{2,1,0} %w)")
ADMIT = ("%ragged-dot.9 = f32[12288,1536]{1,0} custom-call(s32[65]{0} %a, "
         "bf16[12288,2560]{1,0} %xs, bf16[64,2560,1536]{2,1,0} %w)")
ADMIT_FEW = ("%fusion.9 = f32[64,128,1536]{2,1,0} fusion(bf16[128,2560]{1,0}"
             " %x, bf16[64,2560,1536]{2,1,0} %w_in_3_)")
OTHER = "%fusion.1 = f32[48,2560]{1,0} fusion(f32[48,2560]{1,0} %h)"


def test_the_ticks_expert_products_are_found_by_what_they_read():
    ctx = _ctx([TICK_UP, TICK_DOWN, ADMIT, ADMIT_FEW, OTHER, TICK_GROUPED],
               [{"moe_experts_hit": 700}, {"moe_experts_hit": 740}])
    found = [e[2] for e in moe_reduce.expert_events(ctx)]
    assert found == [TICK_UP, TICK_DOWN, TICK_GROUPED]
    t = moe_reduce.tail(ctx)
    assert t == {"ticks": 2.0, "hit": 1440.0, "seconds": 1.5}
    roof = harness.load_reader("moe_experts_roofline.serve_moe").read(ctx)
    assert roof == pytest.approx(
        100 * (1440 * 11_796_480 / 819e9) / 1.5)
    per_tick = harness.load_reader(
        "moe_experts_ms_per_tick.serve_moe").read(ctx)
    assert per_tick == pytest.approx(750.0)


def test_nothing_to_read_is_no_number():
    roof = harness.load_reader("moe_experts_roofline.serve_moe")
    per_tick = harness.load_reader("moe_experts_ms_per_tick.serve_moe")
    hit = [{"moe_experts_hit": 700}]
    for ctx in (_ctx([TICK_UP], [{"lanes": 3}]),          # the parent's ticks
                _ctx([ADMIT, OTHER], hit),                # no tick event
                _ctx([TICK_UP], []),                      # no tick traced
                _ctx([TICK_UP], hit, lanes=0)):           # no decoder
        assert roof.read(ctx) is None and per_tick.read(ctx) is None
    granite = dict(_ctx([TICK_UP], hit), conf={"hidden_size": 2048})
    assert roof.read(granite) is None
    assert roof.read(dict(_ctx([TICK_UP], hit), peaks=None)) is None
    mfu = harness.load_reader("step_mfu.serve_moe")
    assert mfu.read({"peaks": None}) is None
    assert mfu.read({"peaks": {}, "spans": []}) is None


def test_step_mfu_counts_what_a_token_uses_and_the_capped_pairs(monkeypatch):
    from perfbench import span_reduce

    conf = _published()
    ticks = [{"attrs": {"kv_live": 1000}}, {"attrs": {"kv_live": 3000}}]
    admits = [{"attrs": {"prompt_tokens": 8192}}, {"attrs": {}}]
    monkeypatch.setattr(span_reduce, "window_spans",
                        lambda ctx, name: admits)
    ctx = {"peaks": {"bf16_flops": 197e12}, "spans": ticks, "conf": conf,
           "cell": {"chips": 1},
           "window": {"prefill_tokens": 8192, "decode_tokens": 64,
                      "seconds": 2.0}}
    pairs = 12 * 4000 + fl.prefill_layer_pairs(conf, 8192)
    work = 2.0 * 1_067_253_760 * (8192 + 64) + 4.0 * pairs * 128 * 28
    got = harness.load_reader("step_mfu.serve_moe").read(ctx)
    assert got == pytest.approx(100 * work / 2.0 / 197e12)
    monkeypatch.setattr(span_reduce, "window_spans", lambda ctx, name: None)
    assert harness.load_reader("step_mfu.serve_moe").read(ctx) is None


def test_the_reference_reads_a_request_at_the_smallest_width_that_holds_it():
    assert job_serve_moe.width_for(128, 512) == 1024
    assert job_serve_moe.width_for(513, 512) == 1024
    assert job_serve_moe.width_for(514, 512) == 2048
    assert job_serve_moe.width_for(7680, 512) == 8192
    with pytest.raises(ValueError, match="fit none"):
        job_serve_moe.width_for(7682, 512)
    with pytest.raises(ValueError, match="no builder"):
        job_serve_moe.build({"conf": {"model_type": "gpt2"}})


# ---------------------------------------------------------------------------
# the rehearsal: a tiny cell end to end on the CPU
# ---------------------------------------------------------------------------


def _run(manifest, trace=False, seconds=2.0, seed=SEED):
    return run.run_cell(CELL, seed, seconds, trace, manifest=manifest,
                        base=BASE, t_start=time.perf_counter())


@pytest.mark.parametrize("trace", [False, True])
def test_moe_cell_end_to_end(manifest, trace):
    line = json.loads(json.dumps(_run(manifest, trace)))
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 6
    assert line["checks"]["logit_gap"]["tokens"] >= 20
    assert line["checks"]["compiles_in_window"]["value"] == 0
    if trace:
        assert {"decode_tick_p50_ms.serve", "tokens_per_tick.serve",
                "kv_live_share.serve", "queue_wait_p95_ms.serve",
                "setup_build_s.serve"} <= set(line["metrics"])
        # no chip: no share of a peak or of a roofline, no device time; no
        # prefix lookup for a model with a window group
        assert not {"step_mfu.serve_moe", "prefix_hit_share.serve",
                    "moe_experts_roofline.serve_moe",
                    "moe_experts_ms_per_tick.serve_moe",
                    "device_idle.serve"} & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                        "gap_p95_ms", "setup_s"}


def test_fault_a_window_layer_that_sees_every_position(manifest,
                                                       monkeypatch):
    """The planted fault of this job kind: the tick's window layers attend
    every earlier position (the bound is dropped). Answers that run past
    the window then differ from the reference's, and `correct` has to be
    false."""
    from deeplearning4j_tpu.serving import paged

    plain = paged.chunked_attention
    monkeypatch.setattr(
        paged, "chunked_attention",
        lambda q, ck, cv, tables, pos, scale=None, lo=None: plain(
            q, ck, cv, tables, pos, scale=scale))
    monkeypatch.setattr(paged, "_PAGED_TICK_CACHE", {})
    # the table entries behind the window point at trash once the lane has
    # let the blocks go: keep them, so that the fault reads real keys
    monkeypatch.setattr(paged.PagedDecoder, "_trim", lambda self, i: None)
    line = _run(manifest)
    monkeypatch.setattr(paged, "_PAGED_TICK_CACHE", {})
    assert line["correct"] is False
    assert not line["checks"]["logit_gap"]["ok"]


def test_control_fp8_puts_other_tokens_first():
    """The fp8 control at a size a test can hold: over a few hundred
    positions its first token lies below the float32 reference's best by
    more than the toy cell's limit, somewhere; the reference judged by
    itself puts its own best first."""
    import jax

    from perfbench import reference_smallthinker as reference

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
    judged = np.argmax(np.asarray(
        reference.logits_one(params, seq, conf))[19:], -1)
    best = reference.serve_gaps(conf, params, prompt, judged, 64, rows=44)
    assert best[0] == 0    # later rows read another sequence
    with pytest.raises(ValueError, match="unknown lower precision"):
        reference.logits_one(params, seq, conf, "int4")
    with pytest.raises(ValueError, match="does not fit"):
        reference.serve_gaps(conf, params, prompt, served, 32)


def test_the_warm_up_reaches_every_prefill_width_of_the_cell():
    from deeplearning4j_tpu.ops import dispatch
    from perfbench import traffic

    mix = harness.load_cell("serve-smallthinker-stage12-mixed")["mix"]
    lo, hi = mix["user_tokens"]["min"], mix["user_tokens"]["max"]
    every = {dispatch.bucket_size(n) for n in range(lo, hi + 1)}
    warmed = [dispatch.bucket_size(n) for n in traffic.warm_lengths(mix)]
    assert set(warmed) == every and len(every) == 13
    assert max(every) == 8192 and min(every) == 128
