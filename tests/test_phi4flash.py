"""The SambaY decoder-hybrid-decoder (`phi4flash`: models/hybrid.py with
``selective`` (Mamba-1), differential attention over a window and over every
position, ``cross_attention`` and ``gmu`` layers, LayerNorm with bias and
attention biases) against the plain reference (perfbench/
reference_phi4flash.py: float32, Mamba-1 a step at a time, both softmaxes
of a head pair by rows against every key, every layer over every position,
no cache), and through the paged decoder, whose admission stops at the full
attention layer's keys and values.

Everything here is at the tiny size of perfbench/tests/data_sambay (8
layers: Mamba-1, window, Mamba-1, window, Mamba-1 keeping the memory, full,
GMU, cross; d 64; 4 query heads of 16 over 2 KV heads; a Mamba-1 inner width
of 128, state 4, rank 4; window 8; vocabulary 256), block 4, contexts to 48,
on seeded weights that the reference's own ``init_params`` makes, and
compares LOGITS: with random weights the largest logit changes on rounding.

``TIGHT = 5e-5`` under the strict policy (float32 weights, activations,
state and arena): program and reference compute the same float32
mathematics in another order (the scan by chunks against one step at a
time, an online softmax over 128-wide rows against two dense ones) through
8 layers, on logits of magnitude about 1.

Reference anchor: none in the reference (no attention, no state-space
layers in 2016); provenance is the published `phi4flash` config.json, Ren
et al. (SambaY), Sun et al. (YOCO), Ye et al. (differential attention) and
Gu and Dao (Mamba).
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
from deeplearning4j_tpu.ops import pallas_selective
from deeplearning4j_tpu.serving import paged
from perfbench import harness
from perfbench import reference_phi4flash as ref

TIGHT = 5e-5
BT = 4
MAX_LEN = 64
CONF = harness.load_json(os.path.join(
    harness.HERE, "tests", "data_sambay", "configs", "tiny-sambay.json"))


def _model(policy="strict", seed=3, chunk=8, **over):
    conf = dict(CONF, weights_dtype="float32" if policy == "strict"
                else "bfloat16", **over)
    params = ref.init_params(conf, jax.random.PRNGKey(seed))
    cfg = hybrid.HybridConfig.from_published(conf, max_len=MAX_LEN,
                                             dtype_policy=policy)
    cfg = dataclasses.replace(cfg, sel_chunk=chunk)
    return conf, cfg, hybrid.HybridLM(cfg, params)


@pytest.fixture(scope="module")
def strict():
    return _model()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------


def test_from_published_reads_the_family_and_refuses_the_rest():
    cfg = hybrid.HybridConfig.from_published(CONF, max_len=MAX_LEN)
    S, A, G, X = hybrid.SELECTIVE, hybrid.ATTENTION, hybrid.GMU, hybrid.CROSS
    assert cfg.layer_types == (S, A, S, A, S, A, G, X)
    assert cfg.window == (8, 8, 0) and cfg.memory_layer == 4
    assert (cfg.n_selective, cfg.n_attention, cfg.n_cross, cfg.n_gmu) \
        == (3, 3, 1, 1)
    assert (cfg.sel_inner, cfg.sel_state, cfg.sel_dt_rank, cfg.sel_conv) \
        == (128, 4, 4, 4)
    assert cfg.layer_norm and cfg.attn_bias and cfg.diff_attn
    assert cfg.attention_multiplier == 0.25 and cfg.tie_head
    assert cfg.attend_shape == (4, 1, 32) and cfg.admit_layers == 5
    for over, what in (({"rope_theta": 10000.0}, "positions"),
                       ({"partial_rotary_factor": 0.5}, "positions"),
                       ({"mb_per_layer": 3}, "mb_per_layer"),
                       ({"mlp_bias": True}, "mlp_bias"),
                       ({"lm_head_bias": True}, "lm_head_bias"),
                       ({"hidden_act": "gelu"}, "hidden_act"),
                       ({"tie_word_embeddings": False}, "untied"),
                       ({"num_hidden_layers": 7}, "odd number"),
                       ({"max_position_embeddings": 32}, "served context")):
        with pytest.raises(ValueError, match=what):
            hybrid.HybridConfig.from_published(dict(CONF, **over),
                                               max_len=MAX_LEN)


def test_every_family_is_one_entry_of_the_table():
    assert sorted(hybrid.FAMILIES) == ["granitemoehybrid", "phi4flash",
                                       "qwen3_next", "smallthinker"]
    with pytest.raises(ValueError, match="no model_type 'gpt2'"):
        hybrid.HybridConfig.from_published({"model_type": "gpt2"},
                                           max_len=MAX_LEN)


def test_the_published_model_holds_nine_kv_buffers_read_sixteen_times():
    conf = harness.load_json(os.path.join(
        harness.HERE, "configs", "phi-4-mini-flash-reasoning.json"))
    cfg = hybrid.HybridConfig.from_published(conf, max_len=16384)
    needs = opsmem.cache_needs(cfg)
    assert (needs.kv_layers, needs.kv_heads, needs.head_dim) == (9, 10, 128)
    full, window = needs.groups
    assert (full.layer_ids, full.window, full.readers) == ((8,), 0, 8)
    assert (window.layer_ids, window.window, window.readers) \
        == (tuple(range(8)), 512, 1)
    assert [(x.name, x.layers, x.shape, x.dtype) for x in needs.state] == [
        ("selective", 9, (16, 5120), "float32"),
        ("selective_conv", 9, (3, 5120), "bfloat16")]
    assert needs.state_lane_bytes == 9 * 358_400
    # the whole model, held once in bfloat16
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        hybrid.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], int)))
    assert count == conf["parameters"]["total"] == 3_852_562_944
    assert cfg.admit_layers == 17 and cfg.scan_chunks(16384) == 128


def test_the_weights_are_the_programs_layout(strict):
    _conf, cfg, lm = strict
    shapes = hybrid.param_shapes(cfg)
    assert jax.tree.map(lambda a: a.shape, lm.params) == jax.tree.map(
        lambda s: s, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], int))
    own = jax.eval_shape(lambda: hybrid.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, own) \
        == jax.tree.map(lambda a: a.shape, lm.params)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(lm.params)) \
        == ref.param_count(CONF)["total"]


# ---------------------------------------------------------------------------
# the pieces: Mamba-1 by chunks, the 128-wide pair form
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t, n_state, chunk", [
    (24, 24, 8), (21, 17, 8), (40, 33, 16), (13, 13, 64)],
    ids=["whole_chunks", "ragged_and_padded", "two_and_a_half",
         "one_short_chunk"])
def test_selective_chunked_equals_the_stepped_scan(strict, t, n_state,
                                                   chunk):
    """``selective_chunked`` against ``selective_step`` one position at a
    time: the outputs and memories of the positions that feed the state,
    the state and the conv tail as of position n_state - 1, across chunk
    edges, the positions past n_state feeding nothing."""
    _conf, cfg, lm = strict
    cfg = dataclasses.replace(cfg, sel_chunk=chunk)
    sp = hybrid._layer(lm.params["selective"], 1)
    u = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model))
    out, state, tail, y = hybrid.selective_chunked(u, n_state, sp, cfg)
    s = jnp.zeros((1, cfg.sel_state, cfg.sel_inner))
    c = jnp.zeros((1, cfg.sel_conv - 1, cfg.sel_inner))
    outs, ys = [], []
    for i in range(n_state):
        o, s, c, m = hybrid.selective_step(u[i:i + 1], s, c, sp, cfg)
        outs.append(o)
        ys.append(m)
    np.testing.assert_allclose(out[:n_state], np.concatenate(outs),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(y[:n_state], np.concatenate(ys), atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(state, s[0], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(tail, c[0])
    assert float(jnp.abs(state).max()) > 1e-3


@pytest.mark.parametrize("t, chunk", [(40, 16), (300, 128), (7, 8)],
                         ids=["ragged", "three_chunks", "one_chunk"])
def test_the_scan_kernel_equals_the_xla_scan(t, chunk):
    """The Pallas scan, under the interpreter (this test's request: the
    program never interprets), against the XLA form, at two channel tiles
    of 128: the same steps in the same order, float32."""
    rng = np.random.default_rng(t)
    n, inner = 4, 256
    delta = jnp.asarray(rng.uniform(0, 0.5, (t, inner)), jnp.float32)
    du = delta * jnp.asarray(rng.standard_normal((t, inner)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((t, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((t, n)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4, (n, inner)), jnp.float32)
    y0, s0 = pallas_selective.scan_xla(delta, du, b, c, a, chunk)
    y1, s1 = pallas_selective.scan_kernel(delta, du, b, c, a, chunk=chunk,
                                          interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5, rtol=1e-6)
    np.testing.assert_allclose(s1, s0, atol=1e-5, rtol=1e-6)
    assert float(jnp.abs(s0).max()) > 0.1


def _two_softmaxes(q, k, v, pos, scale):
    """Differential attention's two maps written out: q [S, H, hd], k, v
    [T, Hkv, hd] (all lanes read the same keys), lane s seeing keys 0 ..
    pos[s]; pair i reads KV pair i // 2 -> [S, H, 2 hd], row 2i a1 and row
    2i+1 a2 of pair i."""
    s, h, hd = q.shape
    t, hk = k.shape[:2]
    kp = np.repeat(k.reshape(t, hk // 2, 2, hd), 2, axis=1)
    vp = np.repeat(v.reshape(t, hk // 2, 2 * hd), 2, axis=1)
    qp = q.reshape(s, h // 2, 2, hd)
    out = np.zeros((s, h // 2, 2, 2 * hd))
    for lane in range(s):
        seen = slice(0, pos[lane] + 1)
        for which in range(2):
            sc = np.einsum("pd,tpd->pt", qp[lane, :, which],
                           kp[seen, :, which]) * scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[lane, :, which] = np.einsum("pt,tpe->pe", p, vp[seen])
    return out.reshape(s, h, 2 * hd)


@pytest.mark.parametrize("kernel", [False, True], ids=["chunked", "kernel"])
def test_the_padded_pair_form_equals_the_two_softmaxes(kernel, monkeypatch):
    """Through ops/pallas_paged.decode_attention (the chunked gather, and
    the kernel under the interpreter): query rows ``[q1 | 0]`` and ``[0 |
    q2]`` against the arena's row read as KV pairs of 2 hd give each map
    of the pair over both values, at 4 pairs over 2 KV pairs of 64."""
    from deeplearning4j_tpu.ops import pallas_paged

    if kernel:
        call = pallas_paged._decode_call
        monkeypatch.setattr(pallas_paged, "fits", lambda *a: True)
        monkeypatch.setattr(pallas_paged, "_decode_call", lambda *a, **k:
                            call(*a, **dict(k, interpret=True)))
    rng = np.random.default_rng(4)
    lanes, h, hk, hd, bt, m = 3, 8, 4, 64, 4, 6
    q = rng.standard_normal((lanes, h, hd)).astype(np.float32)
    k = rng.standard_normal((m * bt, hk, hd)).astype(np.float32)
    v = rng.standard_normal((m * bt, hk, hd)).astype(np.float32)
    arena = lambda x: jnp.concatenate([jnp.zeros((1, bt, hk * hd)),
                                       jnp.asarray(x).reshape(m, bt, -1)])
    tables = jnp.tile(jnp.arange(1, m + 1, dtype=jnp.int32), (lanes, 1))
    pos = np.asarray([0, 9, m * bt - 1], np.int32)
    got = pallas_paged.decode_attention(
        hybrid._pair_rows(jnp.asarray(q)), arena(k), arena(v), tables,
        jnp.asarray(pos), scale=0.125)
    want = _two_softmaxes(q, k, v, pos, 0.125)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


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


def test_forward_in_bfloat16_stays_nearer_than_fp8():
    """Row by row, against the fp8 control on the same weights: bfloat16's
    rounding moves a row by a few hundredths at this width, fp8 by more."""
    conf, _cfg, lm = _model("performance")
    toks = _tokens(40, seed=1)
    got = np.asarray(lm.logits(toks[None]))[0]
    want = np.asarray(ref.logits_one(lm.params, toks, conf))
    fp8 = np.asarray(ref.logits_one(lm.params, toks, conf, "fp8"))
    rows = np.abs(got - want).max(axis=1)
    assert 1e-4 < np.median(rows) < 0.5 * np.median(
        np.abs(fp8 - want).max(1))


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
        dec._arena, logits = self.step(
            dec.lm.params, dec._arena, jnp.asarray(dec._tok),
            jnp.asarray(dec._pos), jnp.asarray(np.stack(dec._group_tables)))
        return np.asarray(logits)

    def feed(self, lane, token):
        dec = self.dec
        dec._tok[lane] = token
        dec._pos[lane] += 1
        dec._trim(lane)


@pytest.mark.parametrize("lengths", [(9, 30, 20), (17, 3, 33)],
                         ids=["three_lanes", "short_and_long"])
def test_served_logits_equal_the_references_full_forward(strict, lengths):
    """Admission (which stops at the full layer's K and V), then greedy
    ticks through the window and shared pools and the Mamba-1 state pool,
    lanes of different lengths in one tick, past the window: every tick's
    logits against the reference's forward pass over the whole sequence so
    far, every layer over every position."""
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


def test_the_early_stopping_admission_writes_what_a_whole_prefill_does(
        strict):
    """The admission that stops at the full layer's K and V leaves the
    arena (every window and shared buffer) and the Mamba-1 state pool bit
    for bit as the admission that runs every layer over the prompt."""
    _conf, cfg, lm = strict
    dec = paged.PagedDecoder(lm, block_tokens=BT, n_blocks=64, lanes=4)
    dec.stop()
    req = paged._PendingReq(_tokens(27, seed=9), 4, 0.0, 0, 1e18, "default",
                            None, 0)
    buf, _width, write_table, _ = dec._admit_bookkeeping(1, req)
    args = (lm.params, dec._arena, jnp.asarray(buf),
            jnp.asarray(write_table), jnp.asarray([1, dec._pos[1]],
                                                  jnp.int32))
    admit = lambda whole: jax.jit(lambda *a: hybrid.paged_admit(
        *a, cfg, whole=whole))(*args)
    early, whole = admit(False), admit(True)
    assert sorted(early) == ["k", "selective", "selective_conv", "v"]
    for a, b in zip(jax.tree.leaves(early), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(early["k"][2]).max()) > 0.1
    assert float(jnp.abs(early["selective"][0][1]).max()) > 1e-3


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
# the counters and the spans
# ---------------------------------------------------------------------------


def test_the_spans_count_readers_state_and_the_early_stop(strict):
    """``kv_live`` weighs the shared buffer by the layers that read it (the
    full layer and the cross-attention layer) beside the window layers'
    capped positions, over the 3 KV layers; ``shared_kv_live`` is the
    shared pool's live positions times its readers; the Mamba-1 lanes and
    bytes and the admission's layers and chunks are on the spans."""
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
    assert a["admit_layers"] == 5 and a["scan_chunks"] == 4   # 32 by 8
    for step, t in enumerate(ticks):
        attrs = t["attrs"]
        live = 30 + step                  # positions the lane sees
        assert attrs["shared_kv_live"] == 2 * live
        assert attrs["kv_live"] == (2 * min(live, 8) + 2 * live) // 3
        assert attrs["selective_lanes"] == 1
        assert attrs["selective_state_bytes"] == 2 * 3 * 4 * 128 * 4
        assert "ssm_lanes" not in attrs and "gdn_lanes" not in attrs
