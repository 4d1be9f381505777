"""A model of routed experts with window layers beside global ones
(`smallthinker`: models/hybrid.py with ``ffn="experts"``, rotary window
layers, an untied head) against the plain reference
(perfbench/reference_smallthinker.py: float32, a loop over experts under
masks, attention by rows against every key, no cache), and through the
paged decoder with a KV pool a layer kind (ISSUE 37).

Everything here is at the tiny size of perfbench/tests/data_moe (8 layers in
the pattern global, window, window, window; d 64, 4 query heads of 32 over 2
KV heads, 8 experts top-2 of width 32, window 16, vocabulary 256), block 4,
contexts to 48, on seeded weights that the reference's own ``init_params``
makes, and compares LOGITS: with random weights the largest logit changes on
rounding.

Two tolerances, each with its reason:

  * ``TIGHT = 5e-6`` under the strict policy (float32 weights, activations
    and arena): program and reference compute the same float32 mathematics
    in another order (sorted rows or a batched product against a loop over
    experts, an online softmax over chunks of a window against a dense one,
    a padded bucket against the bare sequence), through 8 layers, on logits
    of magnitude about 1.
  * ``BF16 = 0.06`` under the performance policy (bfloat16 weights read by
    both sides, bfloat16 activations into every product and a bfloat16
    arena on the program's side only): 2^-8 relative a rounding, through 8
    layers of two roundings each; the same in float8 reads an order above.
    A routing flip (see the test) is outside it by its nature.

Reference anchor: none in the reference (no attention, no experts in 2016);
provenance is the published `smallthinker` config.json, Su et al. (rotary
positions), Beltagy et al. (sliding windows) and the vLLM block table.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.serving import paged
from perfbench import harness
from perfbench import reference_smallthinker as ref

TIGHT = 5e-6
BF16 = 0.06
BT = 4
MAX_LEN = 64
WINDOW = 16
HELD_MOST = WINDOW // BT + 2
CONF = harness.load_json(os.path.join(
    harness.HERE, "tests", "data_moe", "configs", "tiny-moe.json"))


def _model(policy="strict", seed=3, **over):
    conf = dict(CONF, weights_dtype="float32" if policy == "strict"
                else "bfloat16", **over)
    params = ref.init_params(conf, jax.random.PRNGKey(seed))
    cfg = hybrid.HybridConfig.from_published(conf, max_len=MAX_LEN,
                                             dtype_policy=policy)
    return conf, cfg, hybrid.HybridLM(cfg, params)


@pytest.fixture(scope="module")
def strict():
    return _model()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


class _Offline:
    """A decoder whose worker has been stopped, driven by hand with its own
    bookkeeping (admission, growth, the window's release) and its own
    programs, so that every tick's LOGITS can be read."""

    def __init__(self, lm, **kw):
        self.dec = paged.PagedDecoder(
            lm, **dict(dict(block_tokens=BT, n_blocks=64, lanes=4), **kw))
        self.dec.stop()
        self.step = jax.jit(paged.decode_body(self.dec.cfg))
        self.most_held = 0

    def admit(self, lane, prompt, n_new):
        dec = self.dec
        req = paged._PendingReq(np.asarray(prompt, np.int32), n_new, 0.0, 0,
                                1e18, "default", None, 0)
        buf, width, write_table, _ = dec._admit_bookkeeping(lane, req)
        dec._admit_prefill(lane, buf, width, write_table)
        return width

    def tick(self):
        """One tick for every admitted lane -> logits [lanes, V]."""
        dec = self.dec
        for i, lane in enumerate(dec._slots):
            if lane is not None and dec._grow(i):   # False: preempted
                self.most_held = max(self.most_held,
                                     len(lane.held[1].blocks))
        dec._arena, logits, _hit = self.step(
            dec.lm.params, dec._arena, jnp.asarray(dec._tok),
            jnp.asarray(dec._pos), jnp.asarray(np.stack(dec._group_tables)))
        return np.asarray(logits)

    def feed(self, lane, token):
        dec = self.dec
        dec._tok[lane] = token
        dec._pos[lane] += 1
        dec._trim(lane)


def _served_logits(lm, prompts, steps, others=()):
    """Prefill each prompt into a lane of its own, then `steps` greedy
    ticks of all of them together -> {lane: (logits [steps, V], tokens fed)},
    and the driver. `others` are further prompts that only share the
    ticks."""
    off = _Offline(lm)
    every = list(prompts) + list(others)
    for lane, prompt in enumerate(every):
        off.admit(lane, prompt, steps)
    out = {lane: ([], []) for lane in range(len(every))}
    for _ in range(steps):
        logits = off.tick()
        for lane in out:
            tok = int(logits[lane].argmax())
            out[lane][0].append(logits[lane])
            out[lane][1].append(tok)
            off.feed(lane, tok)
    return {lane: (np.stack(lg), toks) for lane, (lg, toks) in out.items()
            if lane < len(prompts)}, off


# ---------------------------------------------------------------------------
# the model's forward against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("length", [WINDOW - 5, WINDOW, 3 * WINDOW - 4],
                         ids=["below_window", "at_window", "across_window"])
def test_forward_equals_the_reference(strict, length):
    conf, _cfg, lm = strict
    toks = _tokens(length, seed=length)
    got = np.asarray(lm.logits(toks[None]))[0]
    want = np.asarray(ref.logits_one(lm.params, toks, conf))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)


@pytest.mark.parametrize("rows, keys", [(12, 6), (24, 12), (48, 3)],
                         ids=["12x6", "24x12", "one_block_of_chunks"])
def test_attention_by_blocks_over_chunks_equals_the_reference(
        monkeypatch, rows, keys):
    """The admission's attention at the tiles a long prompt is cut into,
    forced at a length a test can hold: blocks of query rows over chunks of
    keys, a window layer's band starting in another chunk than it ends in,
    rows that see nothing of a chunk they walk."""
    monkeypatch.setattr(hybrid, "KEY_CHUNK", keys)
    monkeypatch.setattr(hybrid, "MIN_ROWS", rows)
    monkeypatch.setattr(hybrid, "SCORE_BYTES", 1)
    assert hybrid._attend_tiles(4, 48) == (rows, keys)
    conf, _cfg, lm = _model(seed=7)
    toks = _tokens(48, seed=48)
    got = np.asarray(lm.logits(toks[None]))[0]
    want = np.asarray(ref.logits_one(lm.params, toks, conf))
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)


def test_the_tiles_of_the_served_widths():
    # (query rows a block, keys a chunk) at 28 heads: what fits is one
    # block over one chunk, 8,192 positions go 4 blocks by 4 chunks
    assert [hybrid._attend_tiles(28, t) for t in
            (128, 1536, 2048, 3072, 4096, 6144, 8192)] == [
        (128, 128), (1536, 1536), (2048, 2048), (3072, 1536), (2048, 2048),
        (3072, 1536), (2048, 2048)]
    # the hybrid cell's 32 heads at its widest admission: one pass
    assert hybrid._attend_tiles(32, 2048) == (2048, 2048)


def test_forward_in_bfloat16_stays_within_its_tolerance():
    """Row by row: the usual row differs by bfloat16's rounding; a row
    whose last chosen router logit and the runner-up lie within that
    rounding takes another expert in the program than in the reference (a
    routing flip: both answers are the model's within its precision) and
    differs by a tenth, and the rows that attend to it by a hundredth."""
    conf, _cfg, lm = _model("performance")
    toks = _tokens(40, seed=1)
    got = np.asarray(lm.logits(toks[None]))[0]
    want = np.asarray(ref.logits_one(lm.params, toks, conf))
    rows = np.abs(got - want).max(axis=1)
    assert 1e-4 < np.median(rows) < 0.01
    assert (rows > BF16).mean() <= 0.1 and rows.max() < 0.5


def test_the_window_and_the_rotation_are_live(strict):
    """The reference with the window or the rotary layout taken out differs
    from the model by far more than TIGHT: both are computed, not carried."""
    conf, _cfg, lm = strict
    toks = _tokens(40, seed=2)
    got = np.asarray(lm.logits(toks[None]))[0]
    for key in ("sliding_window_layout", "rope_layout"):
        other = np.asarray(ref.logits_one(
            lm.params, toks, dict(conf, **{key: [0] * 8})))
        assert np.abs(got - other).max() > 1e-2, key


def test_from_published_reads_the_family_and_refuses_the_rest():
    cfg = hybrid.HybridConfig.from_published(CONF, max_len=MAX_LEN)
    assert cfg.layer_types == (hybrid.ATTENTION,) * 8
    assert cfg.rope == (False, True, True, True) * 2
    assert cfg.window == (0, 16, 16, 16) * 2
    assert (cfg.head_dim, cfg.q_dim, cfg.moe_experts, cfg.moe_top_k) \
        == (32, 128, 8, 2)
    assert not cfg.tie_head and cfg.ffn_act == "relu"
    assert cfg.moe_rows_per_token == 16
    held = hybrid.HybridConfig.from_published(dict(CONF, n_layer=4),
                                              max_len=MAX_LEN)
    assert held.n_layers == 4 and held.window == (0, 16, 16, 16)
    for over, what in (({"rope_scaling": {"factor": 2}}, "rope_scaling"),
                       ({"moe_primary_router_apply_softmax": False},
                        "softmax"),
                       ({"max_position_embeddings": 32}, "served context")):
        with pytest.raises(ValueError, match=what):
            hybrid.HybridConfig.from_published(dict(CONF, **over),
                                               max_len=MAX_LEN)
    with pytest.raises(ValueError, match="beside Mamba"):
        hybrid.HybridConfig(layer_types=("mamba", "attention"),
                            ffn="experts", moe_experts=4, moe_top_k=2)
    with pytest.raises(ValueError, match="one entry an attention layer"):
        hybrid.HybridConfig(layer_types=("attention",) * 2, window=(4,))


def test_fit_refuses_and_the_experts_are_one_buffer_a_layer(strict):
    _conf, cfg, lm = strict
    with pytest.raises(NotImplementedError, match="serve-only"):
        lm.fit(None)
    moe = lm.params["moe"]
    assert len(moe["W_in"]) == len(moe["W_down"]) == 8
    assert moe["W_in"][0].shape == (8, 64, 64)
    assert moe["router"].shape == (8, 64, 8)
    assert lm.params["head"].shape == lm.params["embed"].shape
    shapes = hybrid.param_shapes(cfg)
    assert jax.tree.map(lambda a: a.shape, lm.params) == jax.tree.map(
        lambda s: s, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], int))


# ---------------------------------------------------------------------------
# prefill, then decoding through the paged cache, against one full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lengths", [(9, 30, 20), (17, 3, 33)],
                         ids=["window_crossed_in_decode_and_in_prompt",
                              "at_the_window_and_far_past_it"])
def test_served_logits_equal_the_references_full_forward(strict, lengths):
    """Lanes of different lengths in one tick: one crosses the window while
    decoding, one in its prompt, one sits at its edge. Every tick's logits
    against the reference's forward pass over the whole sequence so far."""
    conf, _cfg, lm = strict
    steps = 14
    prompts = [_tokens(n, seed=n) for n in lengths]
    served, off = _served_logits(lm, prompts, steps)
    for lane, prompt in enumerate(prompts):
        logits, toks = served[lane]
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])[:-1]
        want = np.asarray(ref.logits_one(lm.params, seq, conf))[
            len(prompt) - 1:]
        np.testing.assert_allclose(logits, want, atol=TIGHT, rtol=0)
    # contexts reached 47 positions; no lane held more of the window
    # group's blocks than the window reaches
    assert off.most_held <= HELD_MOST
    assert off.most_held >= WINDOW // BT


def test_a_lanes_logits_do_not_depend_on_who_shares_its_tick(strict):
    """The dropless property, served: the same request alone in the pool and
    beside three others (which route their own rows over the same experts
    and page in the same pools)."""
    _conf, _cfg, lm = strict
    prompt = _tokens(21, seed=8)
    alone, _ = _served_logits(lm, [prompt], 10)
    crowd, _ = _served_logits(
        lm, [prompt], 10, others=[_tokens(n, seed=40 + n)
                                  for n in (5, 27, 14)])
    assert alone[0][1] == crowd[0][1]
    np.testing.assert_allclose(crowd[0][0], alone[0][0], atol=TIGHT, rtol=0)


# ---------------------------------------------------------------------------
# the pools: a KV group each
# ---------------------------------------------------------------------------


def test_cache_needs_state_two_groups(strict):
    _conf, cfg, _lm = strict
    needs = opsmem.cache_needs(cfg)
    assert needs.groups == (opsmem.KVGroup((0, 4), 0),
                            opsmem.KVGroup((1, 2, 3, 5, 6, 7), WINDOW))
    assert needs.windowed and not needs.state and needs.kv_per_layer
    assert (needs.kv_layers, needs.kv_heads, needs.head_dim) == (8, 2, 32)
    # K and V x layers x block x KV heads x head x float32
    f32 = np.float32
    assert opsmem.kv_block_bytes(cfg, BT, f32) == 2 * 8 * BT * 2 * 32 * 4
    assert opsmem.kv_block_bytes(cfg, BT, f32, group=0) \
        == 2 * 2 * BT * 2 * 32 * 4 == 4096
    assert opsmem.kv_block_bytes(cfg, BT, f32, group=1) \
        == 2 * 6 * BT * 2 * 32 * 4 == 12288
    assert opsmem.kv_group_blocks(needs, 64, BT, 4) == (64, 4 * HELD_MOST)
    assert opsmem.kv_group_blocks(needs, 10, BT, 4) == (10, 10)
    # a model of one kind of layer states one group, as before
    dense = opsmem.CacheNeeds(3, 2, 8)
    assert dense.groups == (opsmem.KVGroup((0, 1, 2), 0),)
    assert not dense.windowed
    with pytest.raises(ValueError, match="one buffer a layer"):
        opsmem.CacheNeeds(2, 2, 8, groups=(opsmem.KVGroup((0,)),
                                           opsmem.KVGroup((1,), 4)))
    with pytest.raises(ValueError, match="KV layers"):
        opsmem.CacheNeeds(3, 2, 8, kv_per_layer=True,
                          groups=(opsmem.KVGroup((0, 1)),))


def test_kv_report_gives_each_groups_pool(strict):
    _conf, _cfg, lm = strict
    dec = paged.PagedDecoder(lm, block_tokens=BT, n_blocks=64, lanes=4)
    try:
        report = dec.kv_capacity()
        assert report["groups"] == [
            {"layers": 2, "window": 0, "blocks": 64, "blocks_in_use": 0,
             "block_bytes": 4096},
            {"layers": 6, "window": WINDOW, "blocks": 24,
             "blocks_in_use": 0, "block_bytes": 12288}]
        assert report["blocks_total"] == 64 and report["kv_layers"] == 8
        assert report["block_bytes"] == 4096 + 12288
        # the arena: every layer has its group's blocks and the trash block
        assert [a.shape[0] for a in dec._arena["k"]] \
            == [65, 25, 25, 25, 65, 25, 25, 25]
        out = dec.generate(np.stack([_tokens(30, 1), _tokens(30, 2)]), 12,
                           temperature=0.0)
        assert out.shape == (2, 12)
        assert dec.kv_capacity()["groups"][1]["blocks_in_use"] == 0
    finally:
        dec.stop()


def test_a_window_lane_lets_blocks_go_and_another_lane_takes_them(strict):
    _conf, _cfg, lm = strict
    off = _Offline(lm, lanes=2)
    dec = off.dec
    pool = dec._pools[1]
    assert pool.usable == 2 * HELD_MOST
    off.admit(0, _tokens(30, seed=1), 20)
    lane = dec._slots[0]
    # a prompt of 30 at a window of 16: blocks 3 to 7 alone (positions 14
    # on lie in them), every earlier table entry trash
    assert (lane.held[1].first, lane.held[1].nxt) == (3, 8)
    assert not dec._group_tables[1][0, :3].any()
    assert dec._group_tables[1][0, 3:8].all()
    assert len(lane.blocks) == 8 and dec._tables[0, :8].all()
    let_go = []
    for _ in range(12):
        before = set(lane.held[1].blocks)
        logits = off.tick()
        off.feed(0, int(logits[0].argmax()))
        gone = sorted(before - set(lane.held[1].blocks))
        assert pool.refs[gone].sum() == 0     # back in the pool at once
        let_go += gone
        assert len(lane.held[1].blocks) <= HELD_MOST
        assert len(lane.held[1].blocks) < len(lane.blocks)
    # position 41: the window reaches back to 26, block 6
    assert len(let_go) == 3
    assert lane.held[1].first == (int(dec._pos[0]) - WINDOW + 1) // BT == 6
    # the pool hands out what came back last: the lane's own growth took
    # the earlier ones again, another lane's admission takes the newest
    assert set(lane.held[1].blocks) & set(let_go[:-1])
    logits = off.tick()
    off.feed(0, int(logits[0].argmax()))
    off.feed(0, int(logits[0].argmax()))
    newest = set(range(1, pool.usable + 1)) - set(lane.held[1].blocks)
    off.admit(1, _tokens(9, seed=2), 4)
    assert set(dec._slots[1].held[1].blocks) <= newest
    assert pool.in_use == len(lane.held[1].blocks) + 3
    # the global group let nothing go, and a finished lane returns all
    assert len(lane.blocks) == int(dec._pos[0]) // BT + 1
    dec._release_lane(0)
    dec._release_lane(1)
    assert [p.in_use for p in dec._pools] == [0, 0]
    assert not np.stack(dec._group_tables).any()


def test_a_dry_pool_preempts_the_youngest_and_frees_both_groups(strict):
    """Two lanes at an arena that holds one whole context and a block: the
    global pool runs dry as they grow, the younger lane is preempted, and
    its blocks of BOTH groups go back (it is prefilled again later, as any
    preempted lane)."""
    _conf, _cfg, lm = strict
    off = _Offline(lm, lanes=2, n_blocks=MAX_LEN // BT + 1)
    dec = off.dec
    assert dec.group_blocks == (17, 2 * HELD_MOST)
    off.admit(0, _tokens(14, seed=1), 40)
    off.admit(1, _tokens(14, seed=2), 40)
    for _ in range(24):
        logits = off.tick()
        for lane in (0, 1):
            if dec._slots[lane] is not None:
                off.feed(lane, int(logits[lane].argmax()))
    assert dec._slots[0] is not None and dec._slots[1] is None
    assert dec.stats.snapshot()["preemptions"] == 1
    assert dec._total_pending() == 1
    assert [p.in_use for p in dec._pools] \
        == [len(h.blocks) for h in dec._slots[0].held]
    assert not dec._group_tables[1][1].any()


# ---------------------------------------------------------------------------
# what a window group is refused, and what its spans say
# ---------------------------------------------------------------------------


def test_the_paths_that_cannot_carry_a_window_group_refuse_it(strict):
    from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
    from deeplearning4j_tpu.serving.speculate import SpeculativeDecoder

    _conf, _cfg, lm = strict
    with pytest.raises(ValueError, match="scanned ticks.*window"):
        paged.PagedDecoder(lm, block_tokens=BT, n_blocks=64, tick_k=2)
    with pytest.raises(ValueError, match="speculative.*window"):
        SpeculativeDecoder(lm, draft=lm, block_tokens=BT, n_blocks=64)
    with pytest.raises(ValueError, match="serving mesh.*window"):
        MeshPagedDecoder(lm, devices=2, block_tokens=BT, n_blocks=64)
    dec = paged.PagedDecoder(lm, block_tokens=BT, n_blocks=64, lanes=2)
    try:
        with pytest.raises(ValueError, match="handoff.*window"):
            dec.export_prefix(_tokens(20), 4)
        with pytest.raises(ValueError, match="handoff.*window"):
            dec.import_prefix([], np.zeros(0), np.zeros(0))
        # no prefix hit and no lookup: the same prompt twice
        for _ in range(2):
            dec.generate(_tokens(24, seed=5)[None], 3, temperature=0.0)
        stats = dec.stats.snapshot()
        assert stats["prefix_lookups"] == stats["prefix_hits"] == 0
        assert len(dec._prefix) == 0
    finally:
        dec.stop()


def test_the_fixed_slot_pool_refuses_a_model_with_its_own_tick(strict):
    from deeplearning4j_tpu.serving.decode import ContinuousDecoder

    _conf, _cfg, lm = strict
    with pytest.raises(ValueError, match="brings its own tick"):
        ContinuousDecoder(lm, slots=2)


def test_the_pools_still_refuse_capacity_routed_experts():
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from deeplearning4j_tpu.serving.decode import ContinuousDecoder

    lm = TransformerLM(TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_len=16, moe_experts=2, use_flash=False))
    with pytest.raises(ValueError, match="capacity-routed"):
        paged.PagedDecoder(lm, block_tokens=4)
    with pytest.raises(ValueError, match="capacity-routed"):
        ContinuousDecoder(lm, slots=2)


def test_the_spans_count_expert_rows_and_positions_over_groups(strict):
    _conf, cfg, lm = strict
    obs_trace.set_enabled(True)
    obs_trace.tracer().clear()
    dec = paged.PagedDecoder(lm, block_tokens=BT, n_blocks=64, lanes=4)
    try:
        dec.generate(_tokens(30, seed=3)[None], 6, temperature=0.0)
        ticks = [s for s in obs_trace.tracer().spans("serve.batch")
                 if s["attrs"].get("kind") == "decode.paged"]
        admits = obs_trace.tracer().spans("serve.admit")
    finally:
        dec.stop()
        obs_trace.set_enabled(None)
    assert len(ticks) == 6 and len(admits) == 1
    assert admits[0]["attrs"]["moe_rows"] == 30 * 2 * 8
    # no Pallas on the CPU: the admission attends in plain XLA, and says so
    assert admits[0]["attrs"]["attend"] == "xla"
    assert admits[0]["attrs"]["lookup_blocks"] == 7
    chunk = 8 * BT
    for i, t in enumerate(ticks):
        a, pos = t["attrs"], 29 + i
        assert a["moe_rows"] == 1 * 2 * 8
        # one live lane: 2 experts a layer at the most, 8 layers
        assert 8 <= a["moe_experts_hit"] <= 16
        # 2 global layers see pos + 1, 6 window layers 16; over 8 layers
        assert a["kv_live"] == (2 * (pos + 1) + 6 * WINDOW) // 8
        reads = 2 * 4 * (pos // chunk + 1) * chunk \
            + 6 * 4 * (pos // chunk - (pos - 15) // chunk + 1) * chunk
        assert a["kv_read"] == reads // 8


def test_tracing_on_and_off_serve_the_same_tokens(strict):
    """The tick's expert count is read inside ``serve.tick.wait``, after
    its tokens, and only with tracing on: the tokens, greedy and sampled,
    are the same bytes either way."""
    _conf, _cfg, lm = strict
    prompts = [_tokens(n, seed=n) for n in (5, 19, 30)]

    def run():
        dec = paged.PagedDecoder(lm, block_tokens=BT, n_blocks=64, lanes=4)
        try:
            futs = [dec.submit(p, 5, temperature=t, seed=7)
                    for p, t in zip(prompts, (0.0, 0.9, 0.0))]
            return [np.asarray(f.result(timeout=240), np.int32).tobytes()
                    for f in futs]
        finally:
            dec.stop()

    obs_trace.set_enabled(False)
    try:
        obs_trace.tracer().clear()
        off = run()
        assert obs_trace.tracer().spans() == []
        obs_trace.set_enabled(True)
        on = run()
        ticks = [s for s in obs_trace.tracer().spans("serve.batch")
                 if s["attrs"].get("kind") == "decode.paged"]
    finally:
        obs_trace.set_enabled(None)
        obs_trace.tracer().clear()
    assert on == off
    assert ticks and all("moe_experts_hit" in t["attrs"] for t in ticks)


def test_the_engine_reports_and_prices_each_groups_pool(strict):
    """Through `ServingEngine(model=...)`: `/models`' KV report carries the
    groups, and the HBM report prices each group's pool at its own blocks
    (with its trash block), not every layer at the stated `kv_blocks`."""
    from deeplearning4j_tpu.serving.engine import ServingEngine

    _conf, _cfg, lm = strict
    engine = ServingEngine(model=lm, port=0, kv_block=BT, kv_blocks=64,
                           slots=4)
    try:
        [report] = engine.kv_report().values()
        # 16 lanes at this arena: the window pool is the smaller of the
        # stated blocks and what the lanes can hold
        window_pool = min(64, report["lanes"] * HELD_MOST)
        assert [g["blocks"] for g in report["groups"]] == [64, window_pool]
        assert [g["window"] for g in report["groups"]] == [0, WINDOW]
        [priced] = engine.hbm_report()["models"].values()
        assert priced["kv_bytes"] == (64 + 1) * 4096 \
            + (window_pool + 1) * 12288
        out = engine.generate(_tokens(22, seed=9)[None], 5, temperature=0.0)
        assert np.asarray(out).shape == (1, 5)
    finally:
        engine.stop(drain=False)
