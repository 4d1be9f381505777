"""The `serve_hybrid` job kind and what came with it (ISSUE 28): the FLOP and
byte counts of perfbench/flops_granite.py against hand counts, the three new
readers on a recorded fixture, the manifest, and a CPU rehearsal of a tiny
hybrid cell with a planted fault and both controls. The toy cell lives under
perfbench/tests/data_hybrid. Nothing here is a measurement.
"""
import json
import os
import time

import pytest

from perfbench import check_manifest, compare, flops_granite, harness, run

BASE = os.path.join(harness.HERE, "tests", "data_hybrid")
SEED = 2**31 + 91
CELL = "tiny-serve-hybrid"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(BASE, "BENCHMARK.json"))


def _published():
    return harness.load_json(os.path.join(
        harness.HERE, "configs", "granite-4.0-h-micro.json"))


# ---------------------------------------------------------------------------
# operations and bytes by shape
# ---------------------------------------------------------------------------


def test_published_counts_against_the_hand_count():
    conf = _published()
    mamba = (2048 * 8512 + 4096 * 2048 + 4352 * 4 + 4352 + 3 * 64 + 4096
             + 2 * 2048 + 2048 * 16384 + 8192 * 2048)
    attn = (2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 2048 + 2048 * 16384
            + 8192 * 2048)
    assert (mamba, attn) == (76_182_976, 60_821_504)
    total = 36 * mamba + 4 * attn + 100_352 * 2048 + 2048
    assert total == 3_191_396_096
    assert flops_granite.param_count(conf) == total
    assert conf["parameters"]["total"] == total
    assert flops_granite.weight_bytes(conf) == 2 * total
    # 151 MB of state traffic a lane a step: S read once and written once
    step = flops_granite.state_step_bytes(conf)
    assert step["ssm"] == 2 * 36 * 64 * 64 * 128 * 4 == 150_994_944
    assert step["conv"] == 2 * 36 * 3 * 4352 * 2
    assert flops_granite.kv_bytes_per_token(conf) == 8192
    mp = flops_granite.matmul_params(conf)
    assert mp["head"] == 100_352 * 2048
    assert mp["mamba_proj"] == 36 * (2048 * 8512 + 4096 * 2048)
    assert mp["attn_proj"] == 4 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert mp["mlp"] == 40 * 3 * 2048 * 8192


def test_tiny_counts_against_the_hand_count():
    conf = harness.load_json(os.path.join(BASE, "configs",
                                          "tiny-hybrid.json"))
    # d 64, inner 128, conv 160, in 296, 4 mamba + 1 attention layers
    mamba_small = 160 * 4 + 160 + 3 * 8 + 128
    total = (4 * (64 * 296 + 128 * 64 + mamba_small)
             + (2 * 64 * 64 + 2 * 64 * 32)
             + 5 * (3 * 64 * 128 + 2 * 64) + 256 * 64 + 64)
    assert flops_granite.param_count(conf) == total == 264_608
    # one token, attending 10 pairs: 2 x matmul parameters, a step of the
    # scan in 4 layers, one attention layer's scores and values
    matmul = 4 * (64 * 296 + 128 * 64) + (2 * 64 * 64 + 2 * 64 * 32) \
        + 5 * 3 * 64 * 128 + 256 * 64
    scan = 4 * (5 * 8 * 16 * 16 + 2 * 4 * 160)
    assert flops_granite.forward_flops(conf, 1, 10) \
        == 2 * matmul + scan + 1 * 4 * 10 * 16 * 4
    assert flops_granite.state_step_bytes(conf)["ssm"] \
        == 2 * 4 * 8 * 16 * 16 * 4


# ---------------------------------------------------------------------------
# the three readers on a recorded fixture
# ---------------------------------------------------------------------------


class _Trace:
    def __init__(self, ops):
        self.device_ops = {0: ops}


def _ctx():
    conf = _published()
    state = ("%fusion.118 = (f32[64,64,64]{2,1,0:T(8,128)}, "
             "f32[64,64,64,128]{3,2,1,0:T(8,128)}) fusion(%p0, %p1), "
             "kind=kLoop, calls=%fused_computation.118")
    other = "%fusion.7 = f32[64,100352]{1,0:T(8,128)} fusion(%p2)"
    # 2 ticks x 36 layers of 0.4 ms, and other work
    ops, t = [], 0.0
    for _ in range(72):
        ops.append((t, t + 0.4e-3, state))
        ops.append((t + 0.4e-3, t + 0.5e-3, other))
        t += 0.5e-3
    lane = 2 * 36 * 64 * 64 * 128 * 4       # the ssm leaf: 151 MB a step
    ticks = [{"attrs": {"ssm_lanes": 56, "ssm_state_bytes": 56 * lane}},
             {"attrs": {"ssm_lanes": 50, "ssm_state_bytes": 50 * lane}}]
    return {
        "conf": conf, "cell": {"chips": 1}, "trace": _Trace(ops),
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "kv": {"default@1": {"state_lanes": 64}},
        "traced": {"window_s": 0.1, "ticks": ticks},
        "window": {"seconds": 40.0, "prefill_tokens": 30_000,
                   "decode_tokens": 45_000, "attended_pairs": 9.0e6},
    }, lane


def test_ssm_readers_on_a_recorded_fixture():
    ctx, lane = _ctx()
    per_tick = harness.load_reader("ssm_step_ms_per_tick.serve_hybrid")
    roof = harness.load_reader("ssm_step_roofline.serve_hybrid")
    assert per_tick.read(ctx) == pytest.approx(36 * 0.4)
    least = 106 * lane / 819e9
    assert roof.read(ctx) == pytest.approx(100 * least / (72 * 0.4e-3))
    assert 0 < roof.read(ctx) < 100
    # a program without the attributes, a model without state, no chip:
    # nothing, and nothing raised
    bare = dict(ctx, traced={"window_s": 0.1, "ticks": [{"attrs": {}}]})
    assert roof.read(bare) is None and per_tick.read(bare) is None
    assert roof.read(dict(ctx, kv={"default@1": {}})) is None
    assert roof.read(dict(ctx, traced=None)) is None
    assert roof.read(dict(ctx, peaks=None)) is None


def test_step_mfu_on_a_recorded_fixture():
    ctx, _lane = _ctx()
    mfu = harness.load_reader("step_mfu.serve_hybrid")
    work = flops_granite.forward_flops(ctx["conf"], 75_000, 9.0e6)
    assert mfu.read(ctx) == pytest.approx(100 * work / 40.0 / 197e12)
    assert 0 < mfu.read(ctx) < 100
    assert mfu.read(dict(ctx, peaks=None)) is None


# ---------------------------------------------------------------------------
# the manifests
# ---------------------------------------------------------------------------


def test_the_repo_manifest_is_sound_and_holds_the_new_cell():
    manifest = harness.load_manifest()
    assert check_manifest.check(manifest) == []
    cell = "serve-granite-h-micro-chat"
    assert manifest["workloads"][-1]["name"] == cell
    assert manifest["configs"][-1]["reduced"] == []
    only_here = {m["name"] for m in manifest["per_layer"]
                 if m.get("workloads") == [cell]}
    assert only_here == {"step_mfu.serve_hybrid",
                         "ssm_step_roofline.serve_hybrid",
                         "ssm_step_ms_per_tick.serve_hybrid"}
    # the gap's tail spreads by more than half its bound here, and a new
    # cell is admitted only under it (PERF.md section 6, PR 28): the cell
    # reports throughput and the tail of the time to first token, and none
    # of the readers that move `gap_p95_ms`
    e2e = {m["name"] for m in harness.cell_metrics(
        manifest, cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "ttft_p90_ms", "setup_s"}
    per_layer = harness.cell_metrics(manifest, cell, "per_layer")
    assert {m["moves"] for m in per_layer} <= e2e
    mine = {m["name"] for m in per_layer}
    assert "step_mfu.serve" not in mine
    assert "prefix_hit_share.serve" not in mine
    assert {"setup_build_s.serve", "setup_warm_s.serve"} <= mine
    assert len(mine) == 14
    # every number of the catalog's config under the same key
    conf = _published()
    assert conf["layer_types"].count("attention") == 4
    assert conf["max_position_embeddings"] == 131072


def test_the_toy_manifest_is_sound_but_for_its_files(manifest):
    bad = [b for b in check_manifest.check(manifest)
           if "does not exist" not in b and "no reader" not in b
           and "its file says" not in b]
    assert bad == []


# ---------------------------------------------------------------------------
# the rehearsal: a tiny hybrid cell end to end on the CPU
# ---------------------------------------------------------------------------


def _run(manifest, trace=False, seconds=2.0, seed=SEED):
    return run.run_cell(CELL, seed, seconds, trace, manifest=manifest,
                        base=BASE, t_start=time.perf_counter())


@pytest.mark.parametrize("trace", [False, True])
def test_hybrid_cell_end_to_end(manifest, trace):
    line = json.loads(json.dumps(_run(manifest, trace)))
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 6
    # the state pool after the last tick: float32 arithmetic uses every bit
    assert line["checks"]["state_bits_lost"] == {"value": 0.0, "limit": 8.0,
                                                 "ok": True}
    if trace:
        assert {"decode_tick_p50_ms.serve", "tokens_per_tick.serve",
                "kv_live_share.serve", "queue_wait_p95_ms.serve"} \
            <= set(line["metrics"])
        # no chip: no share of a peak or of a roofline, no device time; no
        # prefix lookup for a model with recurrent state
        assert not {"step_mfu.serve_hybrid", "prefix_hit_share.serve",
                    "ssm_step_roofline.serve_hybrid",
                    "ssm_step_ms_per_tick.serve_hybrid",
                    "device_idle.serve"} & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                        "gap_p95_ms", "setup_s"}


def test_fault_a_lane_admitted_without_its_state(manifest, monkeypatch):
    """The planted fault of this job kind: admission writes the blocks and
    leaves the lane's recurrent state as it was. Every answer then starts
    from a state that never saw the prompt, and `correct` has to be false."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.serving import paged

    admit = hybrid.paged_admit

    def stateless(params, arena, window, write_table, lane, cfg):
        out = admit(params, arena, window, write_table, lane, cfg)
        return dict(out, ssm=arena["ssm"], conv=arena["conv"])

    monkeypatch.setattr(hybrid, "paged_admit", stateless)
    monkeypatch.setattr(paged, "_PAGED_ADMIT_CACHE", {})
    line = _run(manifest)
    assert line["correct"] is False
    assert not line["checks"]["logit_gap"]["ok"]


def test_fault_a_state_kept_in_bfloat16(manifest, monkeypatch):
    """The fault that served tokens cannot see: the tick leaves S rounded
    to bfloat16 (what a pool held in bfloat16 would hold). The logits stay
    inside their limits; `state_bits_lost` reads the pool itself, finds 16
    of float32's 23 mantissa bits unused, and `correct` is false."""
    from jax import lax

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.serving import paged

    step = hybrid.mamba_step

    def rounded(u, ssm, tail, mp, cfg):
        out, ssm, tail = step(u, ssm, tail, mp, cfg)
        return out, lax.reduce_precision(ssm, exponent_bits=8,
                                         mantissa_bits=7), tail

    monkeypatch.setattr(hybrid, "mamba_step", rounded)
    monkeypatch.setattr(paged, "_PAGED_TICK_CACHE", {})
    line = _run(manifest)
    checks = line["checks"]
    assert line["correct"] is False
    assert checks["state_bits_lost"]["value"] == 16.0
    assert [k for k, c in checks.items() if not c["ok"]] \
        == ["state_bits_lost"]


def test_mantissa_bits_lost():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.job_serve_hybrid import mantissa_bits_lost as lost

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16, 16), jnp.float32)
    y = x * 0.97 + 0.01 * x[::-1]
    assert lost([x, y]) == 0
    assert lost([y.astype(jnp.bfloat16)]) == 16     # held in bfloat16
    assert lost([y.astype(jnp.bfloat16).astype(jnp.float32)]) == 16
    assert lost([y.astype(jnp.float16).astype(jnp.float32)]) == 13
    # every layer has to hold its bits: the worst one counts; a lane of
    # zeros (never admitted) is not read, a pool of zeros is no reading
    assert lost([y.astype(jnp.bfloat16), y]) == 16
    assert lost([y, y.astype(jnp.bfloat16)]) == 16
    part = y.at[1:].set(0.0)
    assert lost([part.astype(jnp.bfloat16)]) == 16
    assert np.isnan(lost([jnp.zeros((4, 8))])) and np.isnan(lost([]))
    # the reference's scan: float32 as written, bfloat16 under the control
    from perfbench import reference_granite as reference

    cell = harness.load_cell(CELL, BASE)
    params = jax.jit(lambda k: reference.init_params(
        cell["conf"], k, **cell["weights"]))(harness.seed_key(SEED))
    toks = np.random.default_rng(SEED).integers(0, 256, 40)
    read = {lowp: lost([reference.state_one(params, toks, cell["conf"],
                                            lowp)])
            for lowp in (None, "fp8", "state_bf16")}
    assert read == {None: 0, "fp8": 0, "state_bf16": 16}
    limit = cell["limits"]["state_bits_lost"]
    assert read[None] < limit < read["state_bf16"]
    assert harness.load_cell("serve-granite-h-micro-chat")["limits"][
        "state_bits_lost"] == limit


def test_control_fp8_puts_other_tokens_first():
    """The fp8 control at a size a test can hold: over a few hundred
    positions its first token lies below the float32 reference's best by
    more than the toy cell's limit, somewhere; the reference judged by
    itself puts its own best first."""
    import jax
    import numpy as np

    from perfbench import reference_granite as reference

    cell = harness.load_cell(CELL, BASE)
    conf = dict(cell["conf"], vocab_size=4096)
    params = jax.jit(lambda k: reference.init_params(
        conf, k, **cell["weights"]))(harness.seed_key(SEED))
    rng = np.random.default_rng(SEED)
    widest = 0.0
    for _ in range(3):
        prompt = rng.integers(0, 4096, 32)
        served = rng.integers(0, 4096, 90)
        gaps = reference.serve_gaps(conf, params, prompt, served, 128,
                                    lowp="fp8", rows=96)
        assert gaps.shape == (90,) and (gaps >= 0).all()
        widest = max(widest, float(gaps.max()))
    limits = dict(cell["limits"])
    assert widest > limits["logit_gap"]
    seq = np.concatenate([prompt, served])[:-1]
    judged = np.argmax(np.asarray(
        reference.logits_one(params, seq, conf))[31:], -1)
    best = reference.serve_gaps(conf, params, prompt, judged, 128, rows=96)
    assert best[0] == 0    # later rows read another sequence
    assert compare.all_ok(compare.serve_checks([0.0, 0.0], 0, limits))


def test_control_state_bf16_is_live_and_small():
    """The second control rounds S to bfloat16 after every step. It moves
    the logits (it is live), and by far less than fp8 does: with the
    family's initialisation (steps of 1e-3 to 1e-1, D = 1) the state is a
    small part of a layer's output. PERF.md section 2 gives its readings at
    the cell's size and what `correct` can and cannot see of it."""
    import jax
    import numpy as np

    from perfbench import reference_granite as reference

    cell = harness.load_cell(CELL, BASE)
    conf = cell["conf"]
    params = jax.jit(lambda k: reference.init_params(
        conf, k, **cell["weights"]))(harness.seed_key(SEED))
    toks = np.random.default_rng(SEED).integers(0, 256, 96)
    base = np.asarray(reference.logits_one(params, toks, conf))
    moved = {lowp: float(np.abs(np.asarray(reference.logits_one(
        params, toks, conf, lowp)) - base).max())
        for lowp in ("fp8", "state_bf16")}
    assert 0 < moved["state_bf16"] < moved["fp8"] / 4
    with pytest.raises(ValueError, match="unknown lower precision"):
        reference.logits_one(params, toks, conf, "int4")


@pytest.mark.parametrize("cell, widths", [
    ("serve-granite-h-micro-chat", 11), ("serve-590m-chat", 6)])
def test_the_warm_up_reaches_every_prefill_width_of_the_real_cells(cell,
                                                                   widths):
    """`traffic.warm_lengths` steps a quarter of the length at the most, so
    some length falls on every rung of the program's ladder of prefill
    widths that a prompt of the mix can be admitted at (the rung 24 between
    16 and 32 among them, which a step of 16 from 16 went over and which then
    compiled inside the window), in one pass, and on none more than thrice."""
    from deeplearning4j_tpu.ops import dispatch
    from perfbench import traffic

    mix = harness.load_cell(cell)["mix"]
    lo = mix["system_tokens"] + mix["user_tokens"]["min"]
    hi = mix["system_tokens"] + mix["user_tokens"]["max"]
    every = {dispatch.bucket_size(n) for n in range(lo, hi + 1)}
    warmed = [dispatch.bucket_size(n) for n in traffic.warm_lengths(mix)]
    assert set(warmed) == every and len(every) == widths
    assert max(warmed.count(w) for w in every) <= 3
