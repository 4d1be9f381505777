"""Gated DeltaNet linear attention beside gated full attention over routed
experts, a share of them held, and a shared expert (`qwen3_next`:
models/hybrid.py with ``linear_attention`` layers) against the plain
reference (perfbench/reference_qwen3_next.py: float32, the delta rule a
step at a time, attention by rows against every key, each held expert on
its own rows, no cache), and through the paged decoder with a state pool of
GDN leaves beside the KV blocks.

Everything here is at the tiny size of perfbench/tests/data_gdn (8 layers
in the pattern GDN, GDN, GDN, full; d 64; 4 query heads of 32 over 2 KV
heads with 8 rotary dims; 2 GDN key heads and 4 value heads of 16; 4 of 16
experts held, top 4, width 16, a shared expert of 16; vocabulary 256),
block 4, contexts to 48, on seeded weights that the reference's own
``init_params`` makes, and compares LOGITS: with random weights the largest
logit changes on rounding.

Two tolerances, each with its reason:

  * ``TIGHT = 5e-5`` under the strict policy (float32 weights, activations,
    state and arena): program and reference compute the same float32
    mathematics in another order (the delta rule by chunks, through a
    triangular solve, against one step at a time; grouped or batched
    experts against a loop; an online softmax against a dense one) through
    8 layers, on logits of magnitude about 1; the chunked form's solve
    alone moves the sixth digit.
  * ``BF16 = 0.06`` under the performance policy (bfloat16 weights read by
    both sides, bfloat16 activations into every product, a bfloat16 conv
    tail and arena on the program's side only): 2^-8 relative a rounding
    through 8 layers of two roundings each; a routing flip is outside it
    by its nature.

Reference anchor: none in the reference (no attention, no experts, no
linear attention in 2016); provenance is the published `qwen3_next`
config.json, Yang et al. (gated delta networks) and the vLLM block table.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.serving import paged
from perfbench import harness
from perfbench import reference_qwen3_next as ref

TIGHT = 5e-5
BF16 = 0.06
BT = 4
MAX_LEN = 64
CONF = harness.load_json(os.path.join(
    harness.HERE, "tests", "data_gdn", "configs", "tiny-gdn.json"))


def _model(policy="strict", seed=3, chunk=None, **over):
    conf = dict(CONF, weights_dtype="float32" if policy == "strict"
                else "bfloat16", **over)
    params = ref.init_params(conf, jax.random.PRNGKey(seed))
    cfg = hybrid.HybridConfig.from_published(conf, max_len=MAX_LEN,
                                             dtype_policy=policy)
    if chunk:
        cfg = dataclasses.replace(cfg, gdn_chunk=chunk)
    return conf, cfg, hybrid.HybridLM(cfg, params)


@pytest.fixture(scope="module")
def strict():
    return _model(chunk=8)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# ---------------------------------------------------------------------------
# the delta rule: chunks against steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t, n_state, chunk", [
    (24, 24, 8), (21, 17, 8), (40, 33, 16), (13, 13, 64), (64, 50, 64)],
    ids=["whole_chunks", "ragged_and_padded", "two_and_a_half",
         "one_short_chunk", "padded_tail_in_one_chunk"])
def test_gdn_chunked_equals_the_recurrence(strict, t, n_state, chunk):
    """``gdn_chunked`` against ``gdn_step`` one position at a time: the
    outputs of the positions that feed the state, the state and the conv
    tail as of position n_state - 1, where chunks need not divide T and the
    positions past n_state feed nothing."""
    _conf, cfg, lm = strict
    cfg = dataclasses.replace(cfg, gdn_chunk=chunk)
    lp = hybrid._layer(lm.params["gdn"], 1)
    u = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model))
    out, state, tail = hybrid.gdn_chunked(u, n_state, lp, cfg)
    s = jnp.zeros((1, cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim))
    c = jnp.zeros((1, cfg.gdn_conv - 1, cfg.gdn_conv_dim))
    steps = []
    for i in range(n_state):
        o, s, c = hybrid.gdn_step(u[i:i + 1], s, c, lp, cfg)
        steps.append(o)
    np.testing.assert_allclose(out[:n_state], np.concatenate(steps),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(state, s[0], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(tail, c[0])
    assert float(jnp.abs(state).max()) > 0.05


def test_gdn_state_remembers_across_chunks(strict):
    """The state carried between chunks is live: the same last chunk after
    two different histories gives another output."""
    _conf, cfg, lm = strict
    lp = hybrid._layer(lm.params["gdn"], 0)
    u = jax.random.normal(jax.random.PRNGKey(5), (32, cfg.d_model))
    other = u.at[:8].set(jax.random.normal(jax.random.PRNGKey(6),
                                           (8, cfg.d_model)))
    a = hybrid.gdn_chunked(u, 32, lp, cfg)[0][24:]
    b = hybrid.gdn_chunked(other, 32, lp, cfg)[0][24:]
    assert float(jnp.abs(a - b).max()) > 1e-4


# ---------------------------------------------------------------------------
# the model's forward against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("length, chunk", [(11, 8), (40, 8), (48, 64)],
                         ids=["short", "five_chunks", "one_chunk"])
def test_forward_equals_the_reference(length, chunk):
    conf, _cfg, lm = _model(chunk=chunk)
    toks = _tokens(length, seed=length)
    got = np.asarray(lm.logits(toks[None]))[0]
    want = np.asarray(ref.logits_one(lm.params, toks, conf))
    assert np.abs(want).max() > 0.2
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)


def test_forward_in_bfloat16_stays_within_its_tolerance():
    """Row by row, against the fp8 control on the same weights: at this
    width (d 64, sums of a few dozen terms) bfloat16's rounding moves a row
    by a few hundredths, and a routing flip (4 of 16 experts chosen, a
    runner-up within the rounding) by a tenth or two, which the state and
    attention carry to later rows; fp8 moves every row by several
    tenths."""
    conf, _cfg, lm = _model("performance")
    toks = _tokens(40, seed=1)
    got = np.asarray(lm.logits(toks[None]))[0]
    want = np.asarray(ref.logits_one(lm.params, toks, conf))
    fp8 = np.asarray(ref.logits_one(lm.params, toks, conf, "fp8"))
    rows = np.abs(got - want).max(axis=1)
    assert 1e-4 < np.median(rows) < BF16
    assert np.median(rows) < 0.25 * np.median(np.abs(fp8 - want).max(1))
    assert rows[:8].max() < BF16 and rows.max() < 0.5


def test_gated_attention_with_partial_rotary_equals_a_direct_einsum(strict):
    """One attention layer of the model (q | gate out of W_q a head, q and
    k normalised zero-centred a head, rotary on 8 of 32 dims, causal
    softmax, the output under the gate's sigmoid) against the same written
    out by hand with einsums."""
    _conf, cfg, lm = strict
    ap = hybrid._layer(lm.params["attn"], 0)
    t, hd, dims = 12, cfg.head_dim, cfg.rope_dims
    u = jax.random.normal(jax.random.PRNGKey(2), (t, cfg.d_model))
    got, _k, _v = hybrid.attention_full(u, ap, cfg, jnp.float32)

    hi = jax.lax.Precision.HIGHEST
    norm = lambda x, w: x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + cfg.rms_eps) * (1 + w)
    qg = jnp.einsum("td,dq->tq", u, ap["Wq"], precision=hi).reshape(
        t, 4, 2 * hd)
    q, gate = norm(qg[..., :hd], ap["q_norm"]), qg[..., hd:]
    k = norm(jnp.einsum("td,dk->tk", u, ap["Wk"], precision=hi).reshape(
        t, 2, hd), ap["k_norm"])
    v = jnp.einsum("td,dk->tk", u, ap["Wv"], precision=hi).reshape(t, 2, hd)
    ang = np.arange(t)[:, None] * 1e7 ** (-np.arange(dims // 2) * 2 / dims)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]

    def rot(x):
        a, b = x[..., :dims // 2], x[..., dims // 2:dims]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                                x[..., dims:]], -1)

    q, k = rot(q), rot(k)
    kk, vv = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
    sc = jnp.einsum("thd,shd->hts", q, kk, precision=hi) / np.sqrt(hd)
    sc = jnp.where(np.tril(np.ones((t, t), bool)), sc, -jnp.inf)
    att = jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, -1), vv,
                     precision=hi)
    want = jnp.einsum("tq,qd->td", (att * jax.nn.sigmoid(gate)).reshape(
        t, -1), ap["Wo"], precision=hi)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The share test: one expert layer computed by the program four
    times, holding experts 0-3, 4-7, 8-11 and 12-15 of 16 (each chip of an
    expert-parallel layer, no exchange), adds up, with the shared expert
    that every chip computes alike counted once, to the uncut reference
    layer that holds all 16."""
    whole = dict(CONF, num_experts=16)
    params = ref.init_params(whole, jax.random.PRNGKey(11))
    moe = params["moe"]
    h = jax.random.normal(jax.random.PRNGKey(12), (20, CONF["hidden_size"]))
    parts = []
    for first in (0, 4, 8, 12):
        conf = dict(CONF, expert_first=first)
        cfg = hybrid.HybridConfig.from_published(conf, max_len=MAX_LEN,
                                                 dtype_policy="strict")
        share = dict(params, moe=dict(
            moe, W_in=tuple(w[first:first + 4] for w in moe["W_in"]),
            W_down=tuple(w[first:first + 4] for w in moe["W_down"])))
        out, hit, pairs = hybrid._ffn(h, None, share, 2, cfg, "tick.moe")
        parts.append(out - h)
        assert 0 < int(hit) <= 4 and 0 <= int(pairs) <= 20 * 4
    ck, m = ref._frozen(whole), ref.dims(whole)
    x, topi, weight = ref._route(h, moe["norm2"][2], moe["router"][2], ck,
                                 m["k"])
    rows, wts = ref._by_expert(np.asarray(topi), np.asarray(weight), 20, 0,
                               16)
    routed = ref._experts(h, x, jnp.asarray(rows), jnp.asarray(wts),
                          moe["W_in"][2], moe["W_down"][2], None)
    uncut = ref._shared(routed, x, moe["shared_in"][2],
                        moe["shared_down"][2], moe["shared_gate"][2], None)
    shared = ref._shared(h, x, moe["shared_in"][2], moe["shared_down"][2],
                         moe["shared_gate"][2], None) - h
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut - h,
                               atol=1e-5, rtol=0)
    # every (row, expert) pair lands in exactly one share
    assert float(jnp.abs(shared).max()) > 1e-3


def test_from_published_reads_the_family_and_refuses_the_rest():
    cfg = hybrid.HybridConfig.from_published(CONF, max_len=MAX_LEN)
    L, A = hybrid.LINEAR, hybrid.ATTENTION
    assert cfg.layer_types == (L, L, L, A) * 2
    assert (cfg.n_linear, cfg.n_attention, cfg.n_mamba) == (6, 2, 0)
    assert (cfg.head_dim, cfg.rope_dims, cfg.rope) == (32, 8, (True, True))
    assert cfg.attn_gate and cfg.qk_norm and cfg.norm_offset
    assert (cfg.moe_experts, cfg.moe_count, cfg.moe_first, cfg.moe_top_k) \
        == (16, 4, 0, 4) and cfg.moe_share
    assert cfg.shared_expert == 16 and cfg.router_after_mixer
    assert (cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv_dim) \
        == (32, 64, 128)
    assert cfg.moe_rows_per_token == 32 and not cfg.tie_head
    assert hybrid.HybridConfig.from_published(
        dict(CONF, n_layer=4), max_len=MAX_LEN).layer_types == (L, L, L, A)
    for over, what in (({"rope_scaling": {"factor": 4}}, "rope_scaling"),
                       ({"mlp_only_layers": [3]}, "dense feed-forward"),
                       ({"decoder_sparse_step": 2}, "dense feed-forward"),
                       ({"use_sliding_window": True}, "sliding window"),
                       ({"attention_bias": True}, "attention_bias"),
                       ({"hidden_act": "gelu"}, "hidden_act"),
                       ({"norm_topk_prob": False}, "softmax"),
                       ({"n_layer": 9}, "more layers"),
                       ({"partial_rotary_factor": 0.3}, "rotary share"),
                       ({"max_position_embeddings": 32}, "served context")):
        with pytest.raises(ValueError, match=what):
            hybrid.HybridConfig.from_published(dict(CONF, **over),
                                               max_len=MAX_LEN)
    with pytest.raises(ValueError, match="held"):
        hybrid.HybridConfig.from_published(dict(CONF, expert_first=14),
                                           max_len=MAX_LEN)
    with pytest.raises(ValueError, match="value heads"):
        hybrid.HybridConfig.from_published(
            dict(CONF, linear_num_value_heads=3), max_len=MAX_LEN)


def test_the_weights_are_the_programs_layout(strict):
    _conf, cfg, lm = strict
    shapes = hybrid.param_shapes(cfg)
    assert jax.tree.map(lambda a: a.shape, lm.params) == jax.tree.map(
        lambda s: s, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], int))
    assert lm.params["moe"]["W_in"][0].shape == (4, 64, 32)
    assert lm.params["moe"]["router"].shape == (8, 64, 16)
    assert lm.params["gdn"]["W_qkvz"].shape == (6, 64, 2 * 32 + 2 * 64)
    assert lm.params["attn"]["Wq"].shape == (2, 64, 2 * 4 * 32)
    # the program's own weights (tests and examples) are the same tree
    own = jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, own) \
        == jax.tree.map(lambda a: a.shape, lm.params)
    # the configuration's count of what a layer holds
    count = ref.param_count(CONF)
    per = {k: int(sum(np.prod(a.shape) for a in jax.tree.leaves(v)))
           for k, v in lm.params.items()}
    assert sum(per.values()) == count["total"]


# ---------------------------------------------------------------------------
# prefill, then decoding through the paged cache, against one full forward
# ---------------------------------------------------------------------------


class _Offline:
    """A decoder whose worker has been stopped, driven by hand, so that
    every tick's LOGITS can be read."""

    def __init__(self, lm, **kw):
        self.dec = paged.PagedDecoder(
            lm, **dict(dict(block_tokens=BT, n_blocks=64, lanes=4), **kw))
        self.dec.stop()
        self.step = jax.jit(paged.decode_body(self.dec.cfg))
        self.pairs = []

    def admit(self, lane, prompt, n_new):
        dec = self.dec
        req = paged._PendingReq(np.asarray(prompt, np.int32), n_new, 0.0, 0,
                                1e18, "default", None, 0)
        buf, width, write_table, _ = dec._admit_bookkeeping(lane, req)
        dec._admit_prefill(lane, buf, width, write_table)
        return width

    def tick(self):
        dec = self.dec
        for i, lane in enumerate(dec._slots):
            if lane is not None:
                dec._grow(i)
        dec._arena, logits, _hit, pairs = self.step(
            dec.lm.params, dec._arena, jnp.asarray(dec._tok),
            jnp.asarray(dec._pos), jnp.asarray(dec._tables))
        self.pairs.append(int(pairs))
        return np.asarray(logits)

    def feed(self, lane, token):
        self.dec._tok[lane] = token
        self.dec._pos[lane] += 1


@pytest.mark.parametrize("lengths", [(9, 30, 20), (17, 3, 33)],
                         ids=["three_lanes", "short_and_long"])
def test_served_logits_equal_the_references_full_forward(strict, lengths):
    """Admission, then greedy ticks through the arena and the GDN state
    pool, lanes of different lengths in one tick: every tick's logits
    against the reference's forward pass over the whole sequence so far."""
    conf, _cfg, lm = strict
    steps = 12
    prompts = [_tokens(n, seed=n) for n in lengths]
    off = _Offline(lm)
    for lane, prompt in enumerate(prompts):
        off.admit(lane, prompt, steps)
    served = {lane: ([], []) for lane in range(len(prompts))}
    for _ in range(steps):
        logits = off.tick()
        for lane in served:
            tok = int(logits[lane].argmax())
            served[lane][0].append(logits[lane])
            served[lane][1].append(tok)
            off.feed(lane, tok)
    for lane, prompt in enumerate(prompts):
        got, toks = np.stack(served[lane][0]), served[lane][1]
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])[:-1]
        want = np.asarray(ref.logits_one(lm.params, seq, conf))[
            len(prompt) - 1:]
        np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)
    # three live lanes, 8 layers, top 4 of 16 with 4 held: a quarter of
    # the 96 pairs a tick on average, none where no expert is held
    assert 0 < np.mean(off.pairs) < 3 * 8 * 4


def test_a_lanes_logits_do_not_depend_on_who_shares_its_tick(strict):
    _conf, _cfg, lm = strict
    prompt = _tokens(21, seed=8)

    def serve(others):
        off = _Offline(lm)
        for lane, p in enumerate([prompt] + others):
            off.admit(lane, p, 8)
        out = []
        for _ in range(8):
            logits = off.tick()
            out.append(logits[0])
            for lane in range(1 + len(others)):
                off.feed(lane, int(logits[lane].argmax()))
        return np.stack(out)

    alone = serve([])
    crowd = serve([_tokens(n, seed=40 + n) for n in (5, 27, 14)])
    np.testing.assert_allclose(crowd, alone, atol=TIGHT, rtol=0)


# ---------------------------------------------------------------------------
# the state pool, the counters and the spans
# ---------------------------------------------------------------------------


def test_cache_needs_state_the_gdn_leaves(strict):
    _conf, cfg, _lm = strict
    needs = opsmem.cache_needs(cfg)
    assert [(x.name, x.layers, x.shape, x.dtype) for x in needs.state] == [
        ("gdn", 6, (4, 16, 16), "float32"),
        ("gdn_conv", 6, (3, 128), "float32")]
    assert (needs.kv_layers, needs.kv_heads, needs.head_dim) == (2, 2, 32)
    assert needs.state_lane_bytes == 6 * (4 * 16 * 16 + 3 * 128) * 4
    dec = paged.PagedDecoder(_model()[2], block_tokens=BT, n_blocks=64,
                             lanes=4)
    try:
        report = dec.kv_capacity()
        assert report["state_lanes"] == 4
        assert report["state_bytes"] == 4 * needs.state_lane_bytes
        pool = dec.state_pool()
        assert sorted(pool) == ["gdn", "gdn_conv"]
        assert pool["gdn"][0].shape == (4, 4, 16, 16)
    finally:
        dec.stop()


def test_the_spans_count_gdn_state_and_held_pairs(strict):
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
    a = admits[0]["attrs"]
    assert a["moe_rows"] == 30 * 4 * 8
    assert a["scan_chunks"] == 4           # a bucket of 32 in chunks of 8
    lane_s = 6 * 4 * 16 * 16 * 4
    for t in ticks:
        attrs = t["attrs"]
        assert attrs["gdn_lanes"] == 1
        assert attrs["gdn_state_bytes"] == 2 * lane_s
        assert "ssm_lanes" not in attrs and "ssm_state_bytes" not in attrs
        assert 1 <= attrs["moe_experts_hit"] <= 8 * 4
        assert 0 <= attrs["moe_pairs_held"] <= 8 * 4
    # the admission's pairs come with the first tick after it: its 30
    # prompt rows (those past the prompt in the bucket are not counted)
    assert 0 < ticks[0]["attrs"]["admit_moe_pairs_held"] <= 30 * 4 * 8
    assert all(t["attrs"]["admit_moe_pairs_held"] == 0 for t in ticks[1:])


def test_experts_beside_mamba_layers_are_no_longer_refused():
    """The refusal of routed experts beside recurrent layers is gone: a
    Mamba layer's router reads its normalised input as an attention
    layer's does, and the admission unrolls the layers."""
    cfg = hybrid.HybridConfig(
        vocab_size=64, d_model=32, layer_types=("mamba", "attention"),
        n_heads=4, n_kv_heads=2, d_ff=16, max_len=32, ssm_heads=4,
        ssm_head_dim=8, ssm_state=8, ssm_chunk=8, ffn="experts",
        moe_experts=4, moe_top_k=2, tie_head=False, dtype_policy="strict")
    lm = hybrid.HybridLM(cfg)
    out = np.asarray(lm.logits(_tokens(12, seed=2)[None] % 64))
    assert out.shape == (1, 12, 64) and np.isfinite(out).all()
