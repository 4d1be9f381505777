"""A hybrid model (Mamba-2 layers beside grouped-query attention) through
the paged decoder, against the plain reference (ISSUE 28).

The reference is perfbench/reference_granite.py: float32, the recurrence a
step at a time, no chunks, no cache. Everything here is at the tiny size (d
64, 2 + 1 + 2 layers, 4 heads over 2 KV heads, state 16, vocabulary 256) on
seeded weights that the reference's own ``init_params`` makes, and compares
LOGITS: with random weights the largest logit changes on rounding.

Two tolerances, each with its reason:

  * ``TIGHT = 2e-6`` under the strict policy (float32 weights, activations
    and arena): program and reference then compute the same float32
    mathematics in another order (chunks against steps, an online softmax
    against a dense one, a padded bucket against the bare sequence), and
    logits of magnitude 0.1 to 1 differ by a few units in the last place of
    the sums behind them.
  * ``BF16 = 0.03`` under the performance policy (bfloat16 weights read by
    both sides, bfloat16 activations into every product and a bfloat16 conv
    tail and arena on the program's side only): 2^-8 relative per rounding,
    through 5 layers, on logits of magnitude about 1; the same arithmetic
    in float8 would read about 2^-3 per rounding, an order above.

Reference anchor: none in the reference (its recurrent layers are LSTM/GRU
cells, nn/layers/recurrent/); provenance is Dao & Gu, "Transformers are
SSMs" (Mamba-2, the chunked state-space dual) and the vLLM block table.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.serving import paged
from perfbench import reference_granite as ref

TIGHT = 2e-6
BF16 = 0.03
CHUNK = 8
BT = 4
MAX_LEN = 64

CONF = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_chunk_size": CHUNK, "mamba_expand": 2,
    "mamba_n_groups": 1, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8, "rms_norm_eps": 1e-5,
}


def _model(policy="strict", seed=3):
    conf = dict(CONF, weights_dtype="float32" if policy == "strict"
                else "bfloat16")
    params = ref.init_params(conf, jax.random.PRNGKey(seed),
                             residual_gain=4.0)
    cfg = hybrid.HybridConfig.from_published(conf, max_len=MAX_LEN,
                                             dtype_policy=policy)
    return conf, cfg, hybrid.HybridLM(cfg, params)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _decoder(lm, **kw):
    kw = dict(dict(block_tokens=BT, n_blocks=64, lanes=4), **kw)
    return paged.PagedDecoder(lm, **kw)


def _replay(dec, prompt, steps, lane=1, width=None, feed=None):
    """What the decoder does for one request, by hand, with the decoder's
    own admit program and the model's own tick body, so that the logits can
    be read: admission at the bucket width, then ``steps`` ticks. ``feed``
    gives the tokens to feed after the prompt (default: each tick's
    argmax). Returns (logits [steps, V], the arena after admission)."""
    cfg, bt = dec.cfg, dec.block_tokens
    keep = len(prompt)
    if width is None:
        width = min(max(dispatch.bucket_size(keep), keep), cfg.max_len)
    buf = np.zeros((1, width), np.int32)
    buf[0, :keep] = prompt
    table = np.zeros((dec.table_width,), np.int32)
    n_blocks = (keep + steps - 1) // bt + 1
    table[:n_blocks] = 1 + lane * dec.table_width // 4 \
        + np.arange(n_blocks)
    write = table.copy()
    write[(keep - 1) // bt + 1:] = 0
    arena = dec._build_admit(width)(
        dec.lm.params, dec._zero_arena(), jnp.asarray(buf),
        jnp.asarray(write), jnp.asarray([lane, keep - 1], jnp.int32))
    admitted = jax.tree_util.tree_map(np.asarray, arena)
    step = jax.jit(paged.decode_body(cfg))
    tok = np.zeros((dec.lanes,), np.int32)
    pos = np.zeros((dec.lanes,), np.int32)
    tables = np.zeros((dec.lanes, dec.table_width), np.int32)
    tok[lane], pos[lane], tables[lane] = prompt[-1], keep - 1, table
    out = []
    for i in range(steps):
        arena, logits = step(dec.lm.params, arena, jnp.asarray(tok),
                             jnp.asarray(pos), jnp.asarray(tables))
        out.append(np.asarray(logits[lane]))
        tok[lane] = feed[i] if feed is not None else int(out[-1].argmax())
        pos[lane] += 1
    return np.stack(out), admitted


# ---------------------------------------------------------------------------
# the chunked prefill against the sequential scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("length", [CHUNK - 3, CHUNK, 3 * CHUNK - 4],
                         ids=["below", "at", "across"])
def test_chunked_prefill_equals_the_sequential_scan(length):
    conf, _cfg, lm = _model()
    toks = _tokens(length, seed=length)
    got = np.asarray(lm.logits(toks[None]))[0]
    want = np.asarray(ref.logits_one(lm.params, toks, conf))
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)


def test_a_bucket_of_three_chunks_walks_three():
    cfg = hybrid.HybridConfig(ssm_chunk=256)
    assert [cfg.scan_chunks(w) for w in (16, 256, 384, 512)] == [1, 1, 3, 2]


# ---------------------------------------------------------------------------
# prefill of n tokens, then k decode steps, against one full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy,tol", [("strict", TIGHT),
                                        ("performance", BF16)])
def test_prefill_then_decode_equals_the_full_forward(policy, tol):
    conf, cfg, lm = _model(policy)
    n, k = 13, 7                      # the prompt ends inside a chunk
    dec = _decoder(lm)
    try:
        seq = _tokens(n + k, seed=5)
        logits, _ = _replay(dec, seq[:n], k, feed=seq[n:])
        want = np.asarray(ref.logits_one(lm.params, seq, conf))[n - 1:-1]
        np.testing.assert_allclose(logits, want, atol=tol, rtol=0)
        # and the decoder itself serves what those logits put first
        served = dec.generate(seq[None, :n], k, temperature=0.0)[0]
        greedy, _ = _replay(dec, seq[:n], k)
        assert served.tolist() == greedy.argmax(-1).tolist()
    finally:
        dec.stop()


def test_two_bucket_widths_give_the_same_state_and_logits():
    """Padding feeds nothing: a prompt of 11 tokens admitted at width 12
    (one chunk and a half) and at width 24 (three chunks) leaves the lane
    the same state and first logits, to the float32 rounding of sums that
    run over more exact zeros."""
    _conf, _cfg, lm = _model()
    dec = _decoder(lm)
    try:
        prompt = _tokens(11, seed=9)
        a, arena_a = _replay(dec, prompt, 2, width=12)
        b, arena_b = _replay(dec, prompt, 2, width=24)
        for leaf in ("ssm", "conv"):
            for x, y in zip(arena_a[leaf], arena_b[leaf]):
                np.testing.assert_allclose(x[1], y[1], atol=TIGHT, rtol=0)
                assert np.abs(x[1]).max() > 0       # the state was written
                assert not x[0].any() and not x[2].any()   # its lane alone
        np.testing.assert_allclose(a, b, atol=TIGHT, rtol=0)
    finally:
        dec.stop()


# ---------------------------------------------------------------------------
# lanes: admitted at different ticks, released, reused, preempted
# ---------------------------------------------------------------------------


def _alone(lm, prompt, n_new, **kw):
    dec = _decoder(lm, **kw)
    try:
        return dec.generate(prompt[None], n_new, temperature=0.0)[0].tolist()
    finally:
        dec.stop()


def test_lanes_admitted_apart_and_a_reused_lane_give_what_each_gives_alone():
    _conf, _cfg, lm = _model()
    pa, pb, pc = _tokens(9, 1), _tokens(14, 2), _tokens(6, 3)
    want = [_alone(lm, pa, 20), _alone(lm, pb, 4), _alone(lm, pc, 9)]
    dec = _decoder(lm, lanes=2)
    try:
        third = threading.Event()
        seen = []

        def on_a(_tok):
            seen.append(_tok)
            if len(seen) == 3:           # B joins A three ticks in
                third.set()

        fa = dec.submit(pa, 20, temperature=0.0, on_token=on_a)
        assert third.wait(60)
        fb = dec.submit(pb, 4, temperature=0.0)
        got_b = fb.result(timeout=60).tolist()
        # B's lane is free again and still holds B's state: C takes it
        fc = dec.submit(pc, 9, temperature=0.0)
        got = [fa.result(timeout=60).tolist(), got_b,
               fc.result(timeout=60).tolist()]
        assert dec.peak_active == 2
    finally:
        dec.stop()
    assert got == want


def test_a_preempted_lane_requeued_ends_as_an_undisturbed_run():
    """Eight blocks of four tokens cannot hold two requests of 12 + 14
    tokens: the younger is preempted, requeued with what it had generated
    and recomputed from its window (state and blocks both), and both end
    with the tokens of undisturbed runs. Logits behind the tokens: the
    replay of the resumed window against the reference."""
    conf, _cfg, lm = _model()
    pa, pb = _tokens(12, 11), _tokens(12, 12)
    want = [_alone(lm, pa, 14, n_blocks=17), _alone(lm, pb, 14, n_blocks=17)]
    dec = _decoder(lm, lanes=2, n_blocks=17)
    try:
        # 17 blocks is the floor for one max_len sequence; shrink the free
        # list by hand so that two requests cannot both grow
        with dec._cond:
            held = [dec._blocks.alloc() for _ in range(9)]
        fa = dec.submit(pa, 14, temperature=0.0)
        fb = dec.submit(pb, 14, temperature=0.0)
        got = [fa.result(timeout=120).tolist(),
               fb.result(timeout=120).tolist()]
        assert dec.stats.snapshot()["preemptions"] >= 1
        assert held
        # the resumed window's logits, by hand, against the reference
        seq = np.concatenate([pb, np.asarray(got[1][:6], np.int32)])
        logits, _ = _replay(dec, seq, 3, lane=0)
        full = np.concatenate([seq, np.asarray(got[1][6:8], np.int32)])
        ref_logits = np.asarray(ref.logits_one(lm.params, full, conf))
        np.testing.assert_allclose(logits, ref_logits[len(seq) - 1:],
                                   atol=TIGHT, rtol=0)
    finally:
        dec.stop()
    assert got == want


# ---------------------------------------------------------------------------
# grouped-query attention through chunked_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype,tol", [(jnp.float32, TIGHT),
                                          (jnp.bfloat16, 1e-5)])
def test_grouped_attention_through_the_arena_equals_the_references(kv_dtype,
                                                                   tol):
    """q [S, 4, 16] over 2 KV heads, lanes at a chunk's edges, against the
    reference's dense grouped softmax on the arena's values as stored (the
    bfloat16 case: the same rounded K and V on both sides, so what is left
    is float32 order, scaled by values of magnitude 3)."""
    rng = np.random.default_rng(4)
    s, heads, kvh, hd, m = 3, 4, 2, 16, 16
    tables = (1 + np.arange(s * m, dtype=np.int32)).reshape(s, m)
    ck = jnp.asarray(rng.normal(size=(s * m + 1, BT, kvh, hd)), kv_dtype)
    cv = jnp.asarray(rng.normal(size=(s * m + 1, BT, kvh, hd)), kv_dtype)
    q = jnp.asarray(rng.normal(size=(s, heads, hd)), jnp.float32)
    pos = np.asarray([0, paged.ATTN_CHUNK_COLS * BT - 1,
                      paged.ATTN_CHUNK_COLS * BT], np.int32)
    scale = CONF["attention_multiplier"]
    got = np.asarray(paged.chunked_attention(
        q, ck, cv, jnp.asarray(tables), jnp.asarray(pos), scale=scale))
    grp = heads // kvh
    for i in range(s):
        k = np.asarray(ck, np.float32)[tables[i]].reshape(-1, kvh, hd)
        v = np.asarray(cv, np.float32)[tables[i]].reshape(-1, kvh, hd)
        k, v = k[:pos[i] + 1], v[:pos[i] + 1]
        qi = np.asarray(q[i]).reshape(kvh, grp, hd)
        sc = np.einsum("kgd,skd->kgs", qi, k) * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("kgs,skd->kgd", p, v).reshape(heads, hd)
        np.testing.assert_allclose(got[i], want, atol=tol, rtol=0)


# ---------------------------------------------------------------------------
# what the model says it holds, and what refuses it
# ---------------------------------------------------------------------------


def test_cache_needs_price_the_arena_and_the_state_pool():
    _conf, cfg, lm = _model("performance")
    needs = opsmem.cache_needs(cfg)
    assert (needs.kv_layers, needs.kv_heads, needs.head_dim) == (1, 2, 16)
    assert [(x.name, x.layers, x.shape, x.dtype) for x in needs.state] == [
        ("ssm", 4, (8, 16, 16), "float32"),
        ("conv", 4, (3, 160), "bfloat16")]
    assert needs.state_lane_bytes == 4 * (8 * 16 * 16 * 4 + 3 * 160 * 2)
    # K and V of ONE attention layer, two heads of 16, bfloat16
    assert opsmem.kv_block_bytes(cfg, BT) == 2 * 1 * BT * 2 * 16 * 2
    dec = _decoder(lm)
    try:
        cap = dec.kv_capacity()
        assert [a.shape for a in dec._arena["k"]] == [(65, BT, 2 * 16)]
        assert len(dec._arena["ssm"]) == 4
        assert dec._arena["ssm"][0].shape == (4, 8, 16, 16)
        assert dec._arena["conv"][0].dtype == jnp.bfloat16
        assert cap["state_lanes"] == 4
        assert cap["state_bytes"] == 4 * needs.state_lane_bytes
        assert (cap["kv_layers"], cap["kv_heads"]) == (1, 2)
    finally:
        dec.stop()
    # weights once, in the compute dtype, and nothing else resident
    assert not hasattr(lm, "opt")
    leaves = jax.tree_util.tree_leaves(lm.params)
    assert all(x.dtype == jnp.bfloat16 for x in leaves)
    assert opsmem.model_resident_bytes(lm) == 2 * sum(x.size for x in leaves)
    assert sum(x.size for x in leaves) == ref.param_count(_conf)["total"]


@pytest.mark.parametrize("policy,held", [("performance", "bfloat16"),
                                         ("strict", "float32")])
def test_the_decoder_serves_the_models_own_weights(policy, held):
    """The serving view (ISSUE 32) of a model that holds its weights in
    the compute dtype is the model's own tree: no copy, no program."""
    _conf, _cfg, lm = _model(policy)
    dec = _decoder(lm)
    try:
        assert dec._infer_params is lm.params
        cap = dec.kv_capacity()
    finally:
        dec.stop()
    assert (cap["weights_dtype"], cap["weights_view_bytes"]) == (held, 0)


def test_no_prefix_lookup_for_a_model_with_recurrent_state():
    _conf, _cfg, lm = _model()
    dec = _decoder(lm)
    try:
        prompt = _tokens(13, 21)
        first = dec.generate(prompt[None], 3, temperature=0.0)
        again = dec.generate(prompt[None], 3, temperature=0.0)
        snap = dec.stats.snapshot()
        assert (snap["prefix_lookups"], snap["prefix_hits"]) == (0, 0)
        assert len(dec._prefix) == 0
        assert first.tolist() == again.tolist()
    finally:
        dec.stop()


def test_the_paths_that_cannot_carry_state_refuse_loudly():
    from deeplearning4j_tpu.serving.decode import ContinuousDecoder
    from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
    from deeplearning4j_tpu.serving.speculate import SpeculativeDecoder

    _conf, _cfg, lm = _model()
    with pytest.raises(NotImplementedError, match="serve-only"):
        lm.fit(np.zeros((1, 4), np.int32), np.zeros((1, 4), np.int32))
    with pytest.raises(NotImplementedError, match="paged decoder"):
        lm.generate(np.zeros((1, 4), np.int32), 2)
    with pytest.raises(ValueError, match="scanned ticks"):
        paged.PagedDecoder(lm, block_tokens=BT, n_blocks=64, tick_k=2)
    with pytest.raises(ValueError, match="recurrent"):
        SpeculativeDecoder(lm, draft=lm, block_tokens=BT, n_blocks=64)
    with pytest.raises(ValueError, match="recurrent"):
        MeshPagedDecoder(lm, devices=2, block_tokens=BT, n_blocks=64)
    with pytest.raises(ValueError, match="recurrent"):
        ContinuousDecoder(lm)
    dec = _decoder(lm)
    try:
        with pytest.raises(ValueError, match="export_prefix"):
            dec.export_prefix(_tokens(12), 2)
        with pytest.raises(ValueError, match="import_prefix"):
            dec.import_prefix([], np.zeros(0), np.zeros(0))
    finally:
        dec.stop()
    with pytest.raises(ValueError, match="experts"):
        hybrid.HybridConfig.from_published(dict(CONF, num_local_experts=8),
                                           max_len=64)


def test_the_tick_span_counts_state_lanes_and_bytes():
    from deeplearning4j_tpu.obs import trace as obs_trace

    _conf, cfg, lm = _model()
    obs_trace.set_enabled(True)
    obs_trace.tracer().clear()
    dec = _decoder(lm)
    try:
        dec.generate(_tokens(10, 31)[None], 3, temperature=0.0)
        ticks = [s for s in obs_trace.tracer().spans("serve.batch")
                 if s["attrs"].get("kind") == "decode.paged"]
        admits = obs_trace.tracer().spans("serve.admit")
    finally:
        dec.stop()
        obs_trace.set_enabled(None)
        obs_trace.tracer().clear()
    # the ssm leaf alone, read once and written once: the conv tail beside
    # it is not in these bytes (nor its events in the reader's time)
    lane = 4 * 8 * 16 * 16 * 4
    assert lane < opsmem.cache_needs(cfg).state_lane_bytes
    assert len(ticks) == 3
    assert all(t["attrs"]["ssm_lanes"] == 1
               and t["attrs"]["ssm_state_bytes"] == 2 * lane for t in ticks)
    assert [a["attrs"]["scan_chunks"] for a in admits] == [12 // 4]
    assert [a["attrs"]["attend"] for a in admits] == ["xla"]


def test_the_state_pool_is_read_as_the_last_tick_left_it():
    """``PagedDecoder.state_pool`` hands out the pool's own buffers by leaf
    (what the benchmark's ``state_bits_lost`` reads): after a request the
    lane holds the float32 state of the prompt and the tokens fed back, a
    model of KV layers alone has none."""
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)

    conf, cfg, lm = _model()
    prompt = _tokens(11, 5)
    dec = _decoder(lm)
    try:
        assert not np.asarray(dec.state_pool()["ssm"][0]).any()
        out = dec.generate(prompt[None], 4, temperature=0.0)
        pool = dec.state_pool()
        assert sorted(pool) == ["conv", "ssm"]
        assert [b.shape for b in pool["ssm"]] == [(4, 8, 16, 16)] * 4
        assert all(b.dtype == jnp.float32 for b in pool["ssm"])
        assert pool["ssm"][0] is dec._arena["ssm"][0]
        fed = np.concatenate([prompt, np.asarray(out)[0][:3]])
        want = np.asarray(ref.state_one(lm.params, fed, conf))
        got = np.asarray(pool["ssm"][0])
        # dead lanes are advanced too (on token 0): the request's lane is
        # the one that holds its state
        lane = int(np.argmin(np.abs(got - want).sum((1, 2, 3))))
        np.testing.assert_allclose(got[lane], want, atol=TIGHT, rtol=1e-5)
    finally:
        dec.stop()
    gpt = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=32))
    plain = paged.PagedDecoder(gpt, block_tokens=4, n_blocks=16, lanes=2)
    try:
        assert plain.state_pool() == {}
    finally:
        plain.stop()


def test_the_engine_reports_the_state_pools_of_its_decoders():
    from deeplearning4j_tpu.serving.engine import ServingEngine

    _conf, _cfg, lm = _model()
    engine = ServingEngine(model=lm, port=0, kv_blocks=64).start()
    try:
        assert engine.state_pools() == {}     # no decoder built yet
        (key, report), = engine.kv_report().items()
        pools = engine.state_pools()
        assert list(pools) == [key]
        assert len(pools[key]["ssm"]) == 4
        assert pools[key]["ssm"][0].shape[0] == report["state_lanes"]
    finally:
        engine.stop(drain=True)
